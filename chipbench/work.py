"""Work per train step, counted from the graph's node shapes.

``streaming_passes``: every matmul or conv pass that the step hands the
streaming kernel (``kernels/streaming.py``): a node's forward, its weight
gradient, and its input gradient unless it reads the graph's input. Each
pass carries its useful flops (2 per multiply-add, no padding) and the
bytes of its float32 operands read once and its result written once; for
a conv those are the layer's own tensors, not the im2col matrix the
kernel is handed.

``model_flops``: the operations the forward and backward passes require,
the way a model FLOP/s utilization counts them. Every matmul and conv
counts forward, weight gradient and input gradient (two of the three for a
node that reads the graph's input); the one-hot embedding counts as a
gather, zero operations; causal attention counts 12 * heads * head_dim *
seq per token and layer (scores and values, forward and backward, over
the whole S x S matrix the step computes). Recomputed and padded work
never counts.
"""

from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class Pass:
    node: str
    kind: str  # fwd | dw | dx
    m: int
    n: int
    k: int
    flops: float
    bytes: float


def _spec_kind(spec) -> str:
    return type(spec).__name__


def streaming_passes(graph) -> list[Pass]:
    out = []
    for node in graph.nodes:
        s, kind = node.spec, _spec_kind(node.spec)
        has_dx = node.in_edge != graph.input_edge
        if kind in ("MatmulSpec", "EmbeddingSpec"):
            m, n, k = (s.m, s.n, s.k) if kind == "MatmulSpec" else (s.rows, s.d, s.vocab)
            # (name, M, N, K) of C[M,N] = A[M,K] @ B[K,N]
            shapes = [("fwd", m, n, k), ("dw", k, n, m)]
            if has_dx and kind == "MatmulSpec":
                shapes.append(("dx", m, k, n))
            for p, mm, nn, kk in shapes:
                out.append(Pass(node.name, p, mm, nn, kk, 2.0 * mm * nn * kk,
                                F32 * (mm * kk + kk * nn + mm * nn)))
        elif kind == "Conv2dSpec":
            b = graph.batch
            x = b * s.in_h * s.in_w * s.cin
            w = s.kh * s.kw * s.cin * s.cout
            y = b * s.out_h * s.out_w * s.cout
            m, n, k = b * s.out_h * s.out_w, s.cout, s.kh * s.kw * s.cin
            flops = 2.0 * m * n * k
            out.append(Pass(node.name, "fwd", m, n, k, flops, F32 * (x + w + y)))
            out.append(Pass(node.name, "dw", k, n, m, flops, F32 * (x + y + w)))
            if has_dx:
                out.append(Pass(node.name, "dx", m, k, n, flops, F32 * (y + w + x)))
    return out


def model_flops(graph) -> float:
    total = 0.0
    for p in streaming_passes(graph):
        node = next(n for n in graph.nodes if n.name == p.node)
        if _spec_kind(node.spec) != "EmbeddingSpec":
            total += p.flops
    for node in graph.nodes:
        if _spec_kind(node.spec) == "AttentionSpec":
            s = node.spec
            tokens = graph.loss.batch  # one loss row per token
            total += 12.0 * s.n_heads * s.head_dim * s.seq * tokens
    return total


def roofline_s(passes, peaks: dict) -> float:
    """Least time of the passes: each bound by flops or by bytes."""
    return sum(max(p.flops / peaks["flops_per_s"], p.bytes / peaks["hbm_bytes_per_s"])
               for p in passes)
