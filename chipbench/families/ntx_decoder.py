"""Decoder-only LM family: ``NetworkGraph.from_model_config`` graphs.

The configuration file gives the sizes under Hugging Face's key names;
``program_config`` names the program's own ``repro.configs`` entry, whose
widths must agree. The graph's input is one one-hot row per token, built by
the program's own encoder (``repro.lower.one_hot_rows``) inside the feed,
so that encoding counts to the program: that encoder is this adapter's one
assumption about the program's input contract.

The reference is a plain ``jax.numpy`` decoder, independent of the
program: token gather + learned positions, then per layer
LN -> qkv -> causal softmax attention -> proj -> residual, LN -> fc1 ->
ReLU -> fc2 -> residual, a final LN and an untied head, mean softmax
cross-entropy, and SGD. It scans over the stacked layers, so it compiles
once per shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import gen, refmath

UNIT = "tokens"


def sizes(cfg: dict) -> dict:
    c = cfg["config"]
    return {
        "L": c["num_hidden_layers"], "d": c["hidden_size"],
        "H": c["num_attention_heads"], "Dh": c["head_dim"],
        "F": c["intermediate_size"], "V": c["vocab_size"],
        "eps": c["rms_norm_eps"],
    }


def build(cfg: dict, mix: dict):
    """The program's training graph for this configuration and mix."""
    from repro.lower import NetworkGraph
    from repro.models.config import ModelConfig

    s = sizes(cfg)
    if cfg["config"]["num_key_value_heads"] != s["H"]:
        raise ValueError("the NTX decoder has full multi-head attention only")
    mc = ModelConfig(
        name=cfg["name"], family="dense", n_layers=s["L"], d_model=s["d"],
        n_heads=s["H"], n_kv_heads=s["H"], head_dim=s["Dh"], d_ff=s["F"],
        vocab_size=s["V"], norm_type="layer", norm_eps=s["eps"],
    )
    if cfg.get("program_config"):
        from repro.configs import get_config

        ref = get_config(cfg["program_config"])
        fields = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                  "d_ff", "vocab_size")
        off = {f: (getattr(mc, f), getattr(ref, f)) for f in fields
               if getattr(mc, f) != getattr(ref, f)}
        if off:
            raise ValueError(f"{cfg['name']} disagrees with "
                             f"{cfg['program_config']}: {off}")
    t = cfg["train"]
    graph = NetworkGraph.from_model_config(
        mc, batch=mix["batch"], seq=mix["seq"], lr=t["lr"], momentum=t["momentum"])
    if graph.init_std != t["init_std"]:
        raise ValueError(f"graph init_std {graph.init_std} != {t['init_std']}")
    return graph


def units_per_step(mix: dict) -> int:
    return mix["batch"] * mix["seq"]


def pool(cfg: dict, mix: dict, seed: int):
    return gen.token_pool(mix, sizes(cfg)["V"], seed)


def encode(item, graph):
    """One fed batch: the program's one-hot rows of the ids, and targets."""
    from repro.lower import one_hot_rows

    ids, tgt = item
    return one_hot_rows(ids, graph.loss.classes), tgt


def norm_params(cfg: dict) -> set[str]:
    """The LayerNorm (gamma; beta) parameters, which start at (1; 0)."""
    return {f"w_{n}_{i}" for n in ("ln1", "ln2") for i in range(sizes(cfg)["L"])} | {"w_lnf"}


_LAYER = ("ln1", "qkv", "proj", "ln2", "fc1", "fc2")


def _layer_norm(x, w, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w[0] + w[1]


def make_loss(cfg: dict, mix: dict, precision: str):
    """``loss(params, ids, targets)`` of the plain decoder: per-row
    cross-entropy, whose mean is the loss."""
    s = sizes(cfg)
    B, S, H, Dh, eps = mix["batch"], mix["seq"], s["H"], s["Dh"], s["eps"]
    D = H * Dh
    mm = refmath.bilinear(jnp.matmul, precision)
    qk = refmath.bilinear(lambda q, k, precision: jnp.einsum(
        "bhid,bhjd->bhij", q, k, precision=precision), precision)
    pv = refmath.bilinear(lambda p, v, precision: jnp.einsum(
        "bhij,bhjd->bhid", p, v, precision=precision), precision)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(h, w):
        ln1, qkv, proj, ln2, fc1, fc2 = w
        x = mm(_layer_norm(h, ln1, eps), qkv).reshape(B, S, 3, H, Dh)
        q, k, v = (x[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        sc = jnp.where(causal, qk(q, k) * Dh ** -0.5, -jnp.inf)
        ctx = pv(jax.nn.softmax(sc, axis=-1), v)
        h = h + mm(ctx.transpose(0, 2, 1, 3).reshape(B * S, D), proj)
        f = jnp.maximum(mm(_layer_norm(h, ln2, eps), fc1), 0.0)
        return h + mm(f, fc2), None

    def loss(p, ids, tgt):
        h = p["w_emb"][ids] + jnp.tile(p["w_pos"], (B, 1))
        stacked = tuple(
            jnp.stack([p[f"w_{n}_{i}"] for i in range(s["L"])]) for n in _LAYER)
        h, _ = jax.lax.scan(block, h, stacked)
        logits = mm(_layer_norm(h, p["w_lnf"], eps), p["w_head"])
        return refmath.xent(logits, tgt)

    return loss


def ref_inputs(cfg: dict, item):
    ids, tgt = item
    return jnp.asarray(ids, jnp.int32), jnp.asarray(tgt, jnp.int32)


def plant(cfg: dict, mix: dict, item, fault: str):
    """(mix, item) of a step with a planted fault: ``"half"`` leaves the
    second half of the batch out and takes the mean over the rest;
    ``"token"`` alters the first token where it is produced."""
    ids, tgt = item
    if fault == "half":
        half = dict(mix, batch=mix["batch"] // 2)
        n = units_per_step(half)
        return half, (ids[:n], tgt[:n])
    if fault == "token":
        ids = ids.copy()
        ids[0] = (ids[0] + 1) % sizes(cfg)["V"]
        return mix, (ids, tgt)
    raise ValueError(f"unknown fault {fault!r}")
