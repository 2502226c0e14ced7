"""Region fuser: group contiguous train-step blocks into fused kernels.

The graph compiler (:mod:`repro.lower.graph`) emits one command block group
per node pass — ``c1:fwd``, ``r1:fwd``, …, ``loss:dx``, ``c2:dw``, … — and
the Pallas executor used to dispatch one cached ``pallas_call`` per group.
That per-op dispatch is exactly what the NTX datapath avoids: the hardware
streams whole loop nests through the FMAC pipeline (paper §3), so fusing the
software the same way is the hot-path fix.

:func:`plan_fusion` walks the step schedule of a lowered train-step
:class:`~repro.lower.ir.NtxProgram` and greedily groups contiguous
*fusable* steps into :class:`RegionSpec` regions:

  * fwd chains — conv → bias → relu → pool (window == stride) → flatten →
    matmul head, as far as the schedule stays fusable;
  * bwd chains — relu-dX → conv-dW → update → conv-dX runs, crossing layer
    boundaries;
  * SGD/momentum update blocks, fused as the epilogue of the dW that feeds
    them (single-device path only — under a cross-shard gradient reduce the
    psum must run between dW and the update, so updates stay per-node).

Steps with no fusion rule — the maxpool-dX winner scatter, steps touching
spilled regions, the DAG fan-out accumulate steps — become per-node
fallback :class:`Segment`s, so the fused walk stays numerically compatible
with ``run_reference`` on every graph. The softmax-CE loss gradient
``(softmax(z) - onehot) / B``, with each row's loss beside it, is
row-independent and fuses like any other stage, stitching the forward
head chain to the backward dW chain. LM/DAG graphs (attention, layernorm,
residual fan-out) carry *token-row* activations — ``B*S`` rows, not ``B``
— which the batch-tile grid of the region kernel cannot stream correctly,
so their activation passes all run as fallbacks; only the SGD update
epilogues fuse there.

Each region's intermediate edges stay resident in kernel scratch; only
edges read by steps outside the region (or program outputs) escape. The
:class:`RegionSpec` is a frozen dataclass — the region-level
:class:`~repro.lower.executors.PlanCache` key — so fused plans jit once and
retrace zero times, like every per-node plan.

One numerical identity makes bwd chains closed: the relu backward mask can
be taken from the relu *output* (``y > 0`` ⟺ ``x > 0`` for ``y = max(x,
0)``), so pre-activations never need to escape a forward region.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from repro.lower.rules import (
    AttentionSpec,
    BiasSpec,
    Conv2dSpec,
    EmbeddingSpec,
    FlattenSpec,
    LayerNormSpec,
    MatmulSpec,
    MaxPool2dSpec,
    PosEmbedSpec,
    ReluSpec,
    ResidualAddSpec,
)


@dataclass(frozen=True)
class Stage:
    """One node pass inside a fused region (a former per-node dispatch)."""

    node: str
    pass_: str  # "fwd" | "dw" | "upd" | "dx"
    spec: object  # the layer spec (frozen dataclass)
    in_edge: str
    out_edge: str
    param: str | None = None


@dataclass(frozen=True)
class RegionSpec:
    """Plan-cache key for one fused region kernel.

    ``inputs`` are ``(edge, batched)`` pairs — batched edges stream through
    the kernel's double-buffered VMEM tiles, unbatched ones (params,
    momentum state) ride in as resident blocks. ``outputs`` are ``(edge,
    kind)`` with kind ``"batched"`` (written per batch tile) or
    ``"reduced"`` (accumulated across tiles, written on the last grid
    step: dW totals and updated params).
    """

    stages: tuple[Stage, ...]
    batch: int
    lr: float
    momentum: float
    inputs: tuple[tuple[str, bool], ...]
    outputs: tuple[tuple[str, str], ...]

    @property
    def label(self) -> str:
        first, last = self.stages[0], self.stages[-1]
        return (
            f"fused[{first.node}:{first.pass_}..{last.node}:{last.pass_}]"
            f"x{len(self.stages)}"
        )


@dataclass
class Segment:
    """One dispatch of the fused walk: a region or a per-node fallback."""

    region: RegionSpec | None = None
    step: str | None = None


@dataclass
class FusionPlan:
    """plan_fusion's output: the segment walk plus coverage accounting."""

    segments: list[Segment] = field(default_factory=list)
    fused_steps: set[str] = field(default_factory=set)
    fallback_steps: list[str] = field(default_factory=list)
    fused_commands: int = 0
    total_commands: int = 0

    @property
    def n_regions(self) -> int:
        return sum(1 for s in self.segments if s.region is not None)

    @property
    def coverage(self) -> float:
        """Fused commands / total program commands (the gated fraction)."""
        if not self.total_commands:
            return 0.0
        return self.fused_commands / self.total_commands

    def stats(self) -> dict:
        return {
            "regions": self.n_regions,
            "fallback_dispatches": len(self.fallback_steps),
            "fused_steps": len(self.fused_steps),
            "fused_commands": self.fused_commands,
            "total_commands": self.total_commands,
            "coverage": self.coverage,
        }


def step_schedule(graph, keep_grads: bool = True) -> list[str]:
    """The train-step step keys in schedule order (mirrors the lowering)."""
    from repro.lower.graph import edge_consumers

    consumers = edge_consumers(graph)
    keys = [f"{n.name}:fwd" for n in graph.nodes]
    keys.append("loss:dx")
    for node in reversed(graph.nodes):
        if node.param is not None:
            keys.append(f"{node.name}:dw")
            keys.append(f"{node.name}:upd")
        if node.in_edge == graph.input_edge:
            continue
        keys.append(f"{node.name}:dx")
        # fan-out accumulate fires once the forward-FIRST consumer (the
        # last one visited in reverse) has produced its partial
        for e in (node.in_edge, *node.aux_edges):
            cs = consumers.get(e, ())
            if len(cs) > 1 and cs[0] is node:
                keys.append(f"{e}:acc")
    return keys


def unfused_plan(graph) -> FusionPlan:
    """The plan with no regions: every step of the schedule, in order, as
    its own per-node segment (the executor's ``fuse=False`` walk)."""
    keys = step_schedule(graph)
    return FusionPlan(segments=[Segment(step=k) for k in keys], fallback_steps=keys)


def _fusable(node, pass_: str, *, fuse_updates: bool) -> bool:
    """Does this (node, pass) have an in-kernel fusion rule?"""
    s = node.spec
    if pass_ == "fwd":
        if isinstance(s, MaxPool2dSpec):
            # the reshape-max pool tile needs exact window tiling
            return (
                s.window == s.stride
                and s.in_h % s.window == 0
                and s.in_w % s.window == 0
            )
        return isinstance(
            s, (Conv2dSpec, MatmulSpec, BiasSpec, ReluSpec, FlattenSpec)
        )
    if pass_ == "dw":
        return isinstance(s, (Conv2dSpec, MatmulSpec, BiasSpec))
    if pass_ == "upd":
        return fuse_updates
    if pass_ == "dx":
        if isinstance(s, Conv2dSpec):
            # the in-kernel transposed conv dilates dy and pads by k-1-p
            return s.padding <= s.kh - 1 and s.padding <= s.kw - 1
        if isinstance(s, MaxPool2dSpec):
            # first-match winner mask needs the exact reshape tiling too
            return (
                s.window == s.stride
                and s.in_h % s.window == 0
                and s.in_w % s.window == 0
            )
        return isinstance(s, (MatmulSpec, ReluSpec, BiasSpec, FlattenSpec))
    return False


def _step_io(graph, node, pass_: str, *, fused: bool):
    """(reads, writes) edge names of one step, as the fused walk sees them.

    ``fused`` matters for relu-dX: inside a region the mask comes from the
    relu *output* (so pre-activations stay in scratch); the per-node
    fallback plan masks from the input, which must then escape.
    """
    if node is None:  # loss:dx
        return (
            [graph.logits_edge, graph.label_edge],
            [f"d_{graph.logits_edge}", graph.loss_rows_edge],
        )
    s = node.spec
    if pass_ == "fwd":
        reads = [node.in_edge]
        if node.param is not None:
            reads.append(node.param)
        return reads, [node.out_edge]
    if pass_ == "dw":
        p = node.param
        if isinstance(s, BiasSpec):
            return [f"d_{node.out_edge}"], [f"d_{p}"]
        return [node.in_edge, f"d_{node.out_edge}"], [f"d_{p}"]
    if pass_ == "upd":
        p = node.param
        reads = [p, f"d_{p}"]
        writes = [f"{p}_new"]
        if graph.momentum:
            reads.append(f"v_{p}")
            writes.append(f"v_{p}_new")
        return reads, writes
    # dx
    g = f"d_{node.out_edge}"
    if isinstance(s, ReluSpec):
        mask_edge = node.out_edge if fused else node.in_edge
        return [mask_edge, g], [f"d_{node.in_edge}"]
    if isinstance(s, MaxPool2dSpec):
        return [node.in_edge, g], [f"d_{node.in_edge}"]
    if isinstance(s, (Conv2dSpec, MatmulSpec)):
        return [g, node.param], [f"d_{node.in_edge}"]
    return [g], [f"d_{node.in_edge}"]  # bias / flatten reshape


def _touches_spill(graph, node, pass_: str, spilled: set[str]) -> bool:
    """Conservative spill barrier: the step's edges or scratch are spilled."""
    if not spilled:
        return False
    reads, writes = _step_io(graph, node, pass_, fused=True)
    names = set(reads) | set(writes)
    if names & spilled:
        return True
    prefix = f"{node.name}." if node is not None else "loss."
    return any(name.startswith(prefix) for name in spilled)


def _heavy(stages: list[Stage]) -> bool:
    """Is this group worth a fused kernel (vs cheap per-node dispatches)?"""
    if len(stages) >= 2:
        return True
    return any(isinstance(st.spec, (Conv2dSpec, MatmulSpec)) for st in stages)


def plan_fusion(program, *, fuse_updates: bool = True) -> FusionPlan:
    """Plan the fused-region walk for one lowered train-step program.

    ``fuse_updates=False`` keeps every SGD update a per-node dispatch — the
    mesh shard walk needs the cross-shard psum between dW and the update,
    which cannot live inside a shared cached kernel.
    """
    graph = program.meta["graph"]
    keep_grads = program.meta.get("keep_grads", True)
    spilled = set(program.meta.get("spilled", ()))
    keys = program.meta.get("steps") or step_schedule(graph, keep_grads)
    nodes = {n.name: n for n in graph.nodes}
    unbatched = set()
    for p in graph.param_shapes():
        unbatched |= {p, f"v_{p}", f"d_{p}", f"{p}_new", f"v_{p}_new"}

    # LM/DAG graphs carry token-row activations (B*S rows): the region
    # kernel's batch-tile grid would stream only the first B rows, so
    # every activation pass falls back per-node there; SGD update
    # epilogues carry no streamed edges and stay fusable
    token_rows = any(
        isinstance(
            n.spec,
            (AttentionSpec, LayerNormSpec, EmbeddingSpec, PosEmbedSpec,
             ResidualAddSpec),
        )
        for n in graph.nodes
    )

    # 1. classify every step: fusable or per-node fallback
    fusable: dict[str, bool] = {}
    for key in keys:
        name, pass_ = key.split(":")
        node = nodes.get(name)
        if name == "loss":
            ok = not token_rows
            if ok and _touches_spill(graph, None, "dx", spilled):
                ok = False
            fusable[key] = ok
            continue
        if node is None:
            # fan-out accumulate steps ({edge}:acc) have no fusion rule
            fusable[key] = False
            continue
        ok = _fusable(node, pass_, fuse_updates=fuse_updates)
        if ok and pass_ != "upd" and token_rows:
            ok = False
        if ok and _touches_spill(graph, node, pass_, spilled):
            ok = False
        fusable[key] = ok

    # 2. greedy contiguous grouping; groups not worth a kernel demote to
    #    per-node fallbacks before the escape analysis sees them
    groups: list[tuple[bool, list[str]]] = []  # (is_region, step keys)
    for key in keys:
        if fusable[key] and groups and groups[-1][0]:
            groups[-1][1].append(key)
        else:
            groups.append((fusable[key], [key]))

    def _group_stages(ks: list[str]) -> list[Stage]:
        stages = []
        for key in ks:
            name, pass_ = key.split(":")
            if name == "loss":
                stages.append(
                    Stage(
                        node="loss",
                        pass_="dx",
                        spec=graph.loss,
                        in_edge=graph.logits_edge,
                        out_edge=graph.loss_rows_edge,
                        param=graph.label_edge,
                    )
                )
                continue
            node = nodes[name]
            stages.append(
                Stage(
                    node=name,
                    pass_=pass_,
                    spec=node.spec,
                    in_edge=node.in_edge,
                    out_edge=node.out_edge,
                    param=node.param,
                )
            )
        return stages

    groups = [
        (ok and _heavy(_group_stages(ks)), ks) for ok, ks in groups
    ]

    # 3. per-step IO for escape analysis (fallback steps read their
    #    per-node operands, fused relu-dX masks from the relu output)
    key_fused = {key: ok for ok, ks in groups for key in ks}
    io: dict[str, tuple[list[str], list[str]]] = {}
    for key in keys:
        name, pass_ = key.split(":")
        if pass_ == "acc":
            # no-op in the jax walk: consumers' dX steps already
            # accumulated into d_<edge> as they landed
            io[key] = ([], [])
            continue
        node = nodes.get(name) if name != "loss" else None
        io[key] = _step_io(graph, node, pass_, fused=key_fused[key])

    program_outputs = {graph.logits_edge, graph.loss_rows_edge}
    for p in graph.param_shapes():
        program_outputs.add(f"{p}_new")
        if keep_grads:
            program_outputs.add(f"d_{p}")
        if graph.momentum:
            program_outputs.add(f"v_{p}_new")

    readers: dict[str, set[str]] = {}
    for key in keys:
        for edge in io[key][0]:
            readers.setdefault(edge, set()).add(key)

    plan = FusionPlan()
    for is_region, ks in groups:
        if is_region:
            stages = _group_stages(ks)
            in_region = set(ks)
            written: set[str] = set()
            inputs: list[tuple[str, bool]] = []
            outputs: list[tuple[str, str]] = []
            for key in ks:
                reads, writes = io[key]
                for edge in reads:
                    if edge not in written and edge not in {n for n, _ in inputs}:
                        inputs.append((edge, edge not in unbatched))
                for edge in writes:
                    written.add(edge)
            for key in ks:
                for edge in io[key][1]:
                    escapes = edge in program_outputs or any(
                        r not in in_region for r in readers.get(edge, ())
                    )
                    if escapes and edge not in {n for n, _ in outputs}:
                        kind = "reduced" if edge in unbatched else "batched"
                        outputs.append((edge, kind))
            region = RegionSpec(
                stages=tuple(stages),
                batch=graph.batch,
                lr=graph.lr,
                momentum=graph.momentum,
                inputs=tuple(inputs),
                outputs=tuple(outputs),
            )
            plan.segments.append(Segment(region=region))
            plan.fused_steps |= in_region
        else:
            for key in ks:
                plan.segments.append(Segment(step=key))
                plan.fallback_steps.append(key)

    # 4. command-level coverage accounting against the program's blocks
    for block in program.blocks:
        parts = block.tag.split(":")
        step = ":".join(parts[:2]) if len(parts) >= 2 else block.tag
        plan.total_commands += block.n_commands
        if step in plan.fused_steps:
            plan.fused_commands += block.n_commands
    return plan
