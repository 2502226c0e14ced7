"""The network-graph compiler: whole train-step NtxPrograms.

Oracle is jax autodiff on the *same* model: the compiled step's logits,
per-parameter gradients, and updated weights must match ``jax.grad`` +
the SGD(+momentum) update to fp32 tolerance. The liveness allocator is
checked for actual reuse (peak < bump layout) and for the no-aliasing
invariant (regions overlapping in time never overlap in address), and all
three executors must see the same command stream.
"""

import dataclasses

import numpy as np
import pytest

from repro.lower import (
    AttentionSpec,
    EmbeddingSpec,
    LayerNormSpec,
    LivenessAllocator,
    NS_DESIGN,
    NetworkGraph,
    PosEmbedSpec,
    ResidualAddSpec,
    edge_consumers,
    lower,
    lower_training_step,
    paper_cnn_graph,
    run_reference,
    run_timing,
    train_graph,
)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ref  # noqa: E402


def _batch(rng, b, img, n_classes=10):
    x = rng.randn(b, img, img, 3).astype(np.float32)
    labels = rng.randint(0, n_classes, b)
    return x, labels, np.eye(n_classes, dtype=np.float32)[labels]


def _jax_forward(graph, p, x):
    """The paper CNN of ``paper_cnn_graph`` in plain jax (the oracle)."""
    h = ref.conv2d_ref(x, p["w_c1"], stride=2, padding=2)
    h = jax.nn.relu(h)
    h = ref.conv2d_ref(h, p["w_c2"], stride=2, padding=1)
    h = jax.nn.relu(h)
    h = jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )
    h = h.reshape(x.shape[0], -1)
    return h @ p["w_fc"] + p["b_fcb"][None, :]


# ---------------------------------------------------------------------------
# Whole-step oracle: gradients + updated weights vs jax.grad
# ---------------------------------------------------------------------------


def test_train_step_gradients_match_jax_grad():
    graph = paper_cnn_graph(batch=2, img=8, lr=0.05, momentum=0.9)
    prog = lower_training_step(graph)
    rng = np.random.RandomState(0)
    params = graph.init_params(seed=1)
    x, labels, onehot = _batch(rng, 2, 8)
    outs = run_reference(prog, {"x": x, "onehot": onehot, **params})

    def loss_fn(p):
        z = _jax_forward(graph, p, jnp.asarray(x))
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(z) * onehot, axis=1))

    jp = {k: jnp.asarray(v) for k, v in params.items() if not k.startswith("v_")}
    loss, grads = jax.value_and_grad(loss_fn)(jp)

    # logits + the loss rows, each row's cross-entropy
    z = _jax_forward(graph, jp, jnp.asarray(x))
    np.testing.assert_allclose(
        outs[graph.logits_edge], np.asarray(z), rtol=1e-4, atol=1e-5
    )
    rows = outs[graph.loss_rows_edge]
    assert rows.shape == labels.shape
    assert float(np.mean(rows)) == pytest.approx(float(loss), rel=1e-5)

    # per-parameter gradients, momentum, and the updated weights
    for p in graph.param_shapes():
        g = np.asarray(grads[p])
        np.testing.assert_allclose(
            outs[f"d_{p}"], g, rtol=1e-3, atol=1e-5, err_msg=p
        )
        v_new = graph.momentum * params[f"v_{p}"] + g
        np.testing.assert_allclose(
            outs[f"v_{p}_new"], v_new, rtol=1e-3, atol=1e-5, err_msg=p
        )
        np.testing.assert_allclose(
            outs[f"{p}_new"], params[p] - graph.lr * v_new,
            rtol=1e-3, atol=1e-5, err_msg=p,
        )


def test_train_step_plain_sgd_and_ns_design():
    """No-momentum update + the NS design point produce the same numerics."""
    graph = paper_cnn_graph(batch=2, img=8, lr=0.1, momentum=0.0)
    rng = np.random.RandomState(1)
    params = graph.init_params(seed=2)
    x, _labels, onehot = _batch(rng, 2, 8)
    inputs = {"x": x, "onehot": onehot, **params}
    outs = run_reference(lower_training_step(graph), inputs)
    ns_outs = run_reference(
        lower_training_step(graph, design=NS_DESIGN), inputs
    )
    assert "v_w_c1_new" not in outs
    for k in outs:
        np.testing.assert_allclose(
            ns_outs[k], outs[k], rtol=1e-5, atol=1e-6, err_msg=k
        )
    for p in graph.param_shapes():
        np.testing.assert_allclose(
            outs[f"{p}_new"], params[p] - 0.1 * outs[f"d_{p}"],
            rtol=1e-5, atol=1e-6,
        )


# ---------------------------------------------------------------------------
# One program, three executors, identical command streams
# ---------------------------------------------------------------------------


def test_all_executors_consume_one_program():
    from repro.lower import PlanCache, run_pallas

    graph = paper_cnn_graph(batch=2, img=8)
    prog = lower_training_step(graph)
    rng = np.random.RandomState(2)
    params = graph.init_params(seed=3)
    x, _labels, onehot = _batch(rng, 2, 8)
    inputs = {"x": x, "onehot": onehot, **params}

    want = run_reference(prog, inputs)
    ev = run_timing(prog, n_clusters=2, engine="event").summary()
    bl = run_timing(prog, n_clusters=2, engine="block").summary()
    assert ev["n_commands"] == prog.n_commands == bl["n_commands"]
    assert all(ev[k] == bl[k] for k in ev if k != "elided_commands")

    got = run_pallas(prog, inputs, cache=PlanCache())
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k]), want[k], rtol=2e-3, atol=1e-5, err_msg=k
        )


def test_training_decreases_loss_reference_backend():
    graph = paper_cnn_graph(batch=4, img=8, lr=0.1, momentum=0.9)
    rng = np.random.RandomState(3)
    y = rng.randint(0, 10, 4)
    base = np.linspace(0, 3.14 * 4, 8)
    imgs = np.stack([
        np.sin(base[None, :] * (1 + c)) * np.cos(base[:, None] * (1 + c))
        for c in y
    ])[..., None].repeat(3, axis=-1).astype(np.float32)

    res = train_graph(graph, 4, lambda _i: (imgs, y), backend="reference")
    assert res["losses"][-1] < res["losses"][0], res["losses"]


# ---------------------------------------------------------------------------
# The liveness allocator
# ---------------------------------------------------------------------------


def test_liveness_reuse_beats_bump_allocation():
    graph = paper_cnn_graph(batch=2, img=16)
    prog = lower_training_step(graph)
    peak = prog.meta["peak_tcdm_bytes"]
    # bump layout = every distinct storage location laid out back to back
    seen_bases = set()
    bump = 0
    for r in prog.regions.values():
        if r.base not in seen_bases:
            seen_bases.add(r.base)
            bump += r.bytes
    assert peak < bump, (peak, bump)
    assert peak <= prog.meta["tcdm_budget_bytes"]


def test_no_region_aliasing_across_live_intervals():
    graph = paper_cnn_graph(batch=2, img=8)
    prog = lower_training_step(graph)
    intervals = prog.meta["intervals"]
    regions = prog.regions
    names = list(intervals)
    for i, a in enumerate(names):
        ra, (sa, ea) = regions[a], intervals[a]
        for b in names[i + 1:]:
            rb, (sb, eb) = regions[b], intervals[b]
            overlap_time = not (ea < sb or eb < sa)
            overlap_addr = not (ra.end <= rb.base or rb.end <= ra.base)
            if overlap_time and overlap_addr:
                # the only legal address sharing is an explicit alias view,
                # which shares the full storage window exactly
                assert ra.base == rb.base and ra.size == rb.size, (
                    f"{a}{intervals[a]}@[{ra.base},{ra.end}) aliases "
                    f"{b}{intervals[b]}@[{rb.base},{rb.end})"
                )


def test_allocator_spills_over_budget_and_execution_is_identical():
    graph = paper_cnn_graph(batch=2, img=16)
    full = lower_training_step(graph, n_clusters=16)
    tiny = lower_training_step(graph, n_clusters=1)
    assert not full.meta["spilled"]
    assert tiny.meta["spilled"]
    assert tiny.meta["peak_tcdm_bytes"] <= tiny.meta["tcdm_budget_bytes"]
    spills = [b for b in tiny.blocks if b.tag.startswith(("spill:", "fill:"))]
    assert spills and all(
        b.dma_bytes_in + b.dma_bytes_out > 0 for b in spills
    )
    rng = np.random.RandomState(4)
    params = graph.init_params(seed=4)
    x, _labels, onehot = _batch(rng, 2, 16)
    inputs = {"x": x, "onehot": onehot, **params}
    a = run_reference(full, inputs)
    b = run_reference(tiny, inputs)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# Transformer node lowerings: per-node oracle round-trips
# ---------------------------------------------------------------------------


def test_attention_matches_jax_vjp():
    S, H, Dh = 6, 2, 4
    D = H * Dh
    spec = AttentionSpec(S, H, Dh)
    rng = np.random.RandomState(5)
    qkv = rng.randn(S, 3 * D).astype(np.float32)

    def oracle(qkv):
        def heads(m):
            return m.reshape(S, H, Dh).transpose(1, 0, 2)

        q, k, v = (heads(qkv[:, i * D:(i + 1) * D]) for i in range(3))
        sc = jnp.einsum("hid,hjd->hij", q, k) * (Dh ** -0.5)
        mask = jnp.where(jnp.tril(jnp.ones((S, S))) > 0, 0.0, -1e9)
        pr = jax.nn.softmax(sc + mask[None], axis=-1)
        return jnp.einsum("hij,hjd->hid", pr, v).transpose(1, 0, 2).reshape(S, D)

    outs = run_reference(lower(spec, "fwd"), {"x": qkv})
    want_y, vjp = jax.vjp(oracle, jnp.asarray(qkv))
    np.testing.assert_allclose(
        outs["y"], np.asarray(want_y), rtol=1e-4, atol=1e-5
    )
    dctx = rng.randn(S, D).astype(np.float32)
    outs = run_reference(lower(spec, "dx"), {"x": qkv, "dy": dctx})
    np.testing.assert_allclose(
        outs["dx"], np.asarray(vjp(jnp.asarray(dctx))[0]),
        rtol=1e-4, atol=1e-5,
    )


def test_layernorm_matches_jax_vjp():
    rows, d, eps = 10, 8, 1e-5
    spec = LayerNormSpec(rows, d, eps)
    rng = np.random.RandomState(6)
    x = rng.randn(rows, d).astype(np.float32)
    w = rng.randn(2, d).astype(np.float32)  # row0=gamma, row1=beta

    def oracle(x, w):
        mu = jnp.mean(x, axis=1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w[0] + w[1]

    want_y, vjp = jax.vjp(oracle, jnp.asarray(x), jnp.asarray(w))
    outs = run_reference(lower(spec, "fwd"), {"x": x, "w": w})
    np.testing.assert_allclose(
        outs["y"], np.asarray(want_y), rtol=1e-4, atol=1e-5
    )
    dy = rng.randn(rows, d).astype(np.float32)
    want_dx, want_dw = vjp(jnp.asarray(dy))
    outs = run_reference(lower(spec, "dw"), {"x": x, "dy": dy})
    np.testing.assert_allclose(
        outs["dw"], np.asarray(want_dw), rtol=1e-4, atol=1e-5
    )
    outs = run_reference(lower(spec, "dx"), {"x": x, "w": w, "dy": dy})
    np.testing.assert_allclose(
        outs["dx"], np.asarray(want_dx), rtol=1e-4, atol=1e-5
    )


def test_residual_embedding_posembed_match_oracles():
    rng = np.random.RandomState(7)
    a = rng.randn(5, 7).astype(np.float32)
    b = rng.randn(5, 7).astype(np.float32)
    rs = ResidualAddSpec((5, 7))
    np.testing.assert_allclose(
        run_reference(lower(rs, "fwd"), {"x": a, "x2": b})["y"], a + b,
        rtol=1e-6,
    )
    # d(x + x2)/dx is the identity on both inputs
    np.testing.assert_allclose(
        run_reference(lower(rs, "dx"), {"dy": a})["dx"], a, rtol=1e-6
    )

    emb = EmbeddingSpec(rows=6, vocab=11, d=5)
    oh = np.eye(11, dtype=np.float32)[rng.randint(0, 11, 6)]
    W = rng.randn(11, 5).astype(np.float32)
    np.testing.assert_allclose(
        run_reference(lower(emb, "fwd"), {"x": oh, "w": W})["y"], oh @ W,
        rtol=1e-4, atol=1e-5,
    )
    dy = rng.randn(6, 5).astype(np.float32)
    np.testing.assert_allclose(
        run_reference(lower(emb, "dw"), {"x": oh, "dy": dy})["dw"],
        oh.T @ dy, rtol=1e-4, atol=1e-5,
    )

    pe = PosEmbedSpec(batch=3, seq=4, d=5)
    x3 = rng.randn(3, 4, 5).astype(np.float32)
    P = rng.randn(4, 5).astype(np.float32)
    np.testing.assert_allclose(
        run_reference(lower(pe, "fwd"), {"x": x3, "w": P})["y"],
        x3 + P[None], rtol=1e-5,
    )
    dy3 = rng.randn(3, 4, 5).astype(np.float32)
    np.testing.assert_allclose(
        run_reference(lower(pe, "dw"), {"dy": dy3})["dw"], dy3.sum(0),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        run_reference(lower(pe, "dx"), {"dy": dy3})["dx"], dy3, rtol=1e-6
    )


# ---------------------------------------------------------------------------
# The DAG compiler: tiny transformer vs jax.grad, branching liveness
# ---------------------------------------------------------------------------


def _tiny_lm(batch=2, seq=6, n_layers=2):
    from repro.models.config import ModelConfig

    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=n_layers, d_model=16,
        n_heads=2, n_kv_heads=2, head_dim=8, d_ff=32, vocab_size=13,
    )
    return NetworkGraph.from_model_config(cfg, batch=batch, seq=seq, lr=0.05)


def test_lm_train_step_gradients_match_jax_grad():
    from repro.launch.train import _dag_oracle_loss

    graph = _tiny_lm()
    prog = lower_training_step(graph)
    params = graph.init_params(seed=1)
    rng = np.random.RandomState(8)
    V = graph.loss.classes
    eye = np.eye(V, dtype=np.float32)
    x = eye[rng.randint(0, V, graph.loss.batch)]
    onehot = eye[rng.randint(0, V, graph.loss.batch)]
    outs = run_reference(
        prog, {graph.input_edge: x, graph.label_edge: onehot, **params}
    )

    jp = {k: jnp.asarray(v) for k, v in params.items()}
    grads = jax.grad(
        lambda p: _dag_oracle_loss(graph, p, jnp.asarray(x),
                                   jnp.asarray(onehot))
    )(jp)
    for p in graph.param_shapes():
        g = np.asarray(grads[p])
        np.testing.assert_allclose(
            outs[f"d_{p}"], g, rtol=1e-4, atol=1e-5, err_msg=p
        )
        np.testing.assert_allclose(
            outs[f"{p}_new"], params[p] - graph.lr * g,
            rtol=1e-4, atol=1e-5, err_msg=p,
        )


def test_lm_dag_liveness_and_gradient_accumulation():
    graph = _tiny_lm(n_layers=1)
    prog = lower_training_step(graph)

    # residual fan-out: the skip edges feed both a layernorm and an add
    multi = {e: [n.name for n in ns]
             for e, ns in edge_consumers(graph).items() if len(ns) > 1}
    assert multi, "expected residual fan-out edges"
    for e, names in multi.items():
        assert len(names) == 2, (e, names)
    # ... and each fan-out edge gets an explicit partial-accumulation step
    acc_tags = {b.tag for b in prog.blocks if ":acc:" in b.tag}
    assert {t.split(":")[0] for t in acc_tags} == set(multi)

    # the liveness allocator invariants must survive branching lifetimes
    assert prog.meta["peak_tcdm_bytes"] <= prog.meta["tcdm_budget_bytes"]
    seen_bases, bump = set(), 0
    for r in prog.regions.values():
        if r.base not in seen_bases:
            seen_bases.add(r.base)
            bump += r.bytes
    assert prog.meta["peak_tcdm_bytes"] < bump
    intervals, regions = prog.meta["intervals"], prog.regions
    names = list(intervals)
    for i, a in enumerate(names):
        ra, (sa, ea) = regions[a], intervals[a]
        for b in names[i + 1:]:
            rb, (sb, eb) = regions[b], intervals[b]
            if not (ea < sb or eb < sa):  # live at the same time
                assert (ra.end <= rb.base or rb.end <= ra.base
                        or (ra.base == rb.base and ra.size == rb.size)), (
                    f"{a} aliases {b}"
                )


def test_sequential_is_deprecated_alias_of_chain():
    from repro.lower.rules import FlattenSpec, MatmulSpec

    layers = [("flat", "flatten"), ("fc", MatmulSpec(2, 10, 12))]
    with pytest.warns(DeprecationWarning, match="from_model_config"):
        old = NetworkGraph.sequential("t", 2, (3, 4), layers)
    new = NetworkGraph.chain("t", 2, (3, 4), layers)
    assert old == new


def test_liveness_allocator_unit():
    l = LivenessAllocator(budget_words=100)
    x = l.alloc("x", (40,), "input", start=0, end=2)
    y = l.alloc("y", (30,), "scratch", start=1, end=3)
    z = l.alloc("z", (35,), "scratch", start=3, end=5)
    assert z.base == x.base  # x died at 2 -> its hole is recycled
    assert l.peak_tcdm_words == 70
    s = l.alloc("s", (50,), "scratch", start=3, end=4)  # nothing fits
    assert "s" in l.spilled and s.base >= 100
    assert y.base == 40  # live regions were never moved
    f = l.alias("f", "z", (5, 7), "scratch", end=9)
    assert f.base == z.base and f.size == z.size


def test_one_hot_rows_equal_identity_rows():
    from repro.lower import one_hot_rows

    idx = np.array([3, 0, 7, 3, 9])
    rows = one_hot_rows(idx, 10)
    assert rows.dtype == np.float32
    np.testing.assert_array_equal(rows, np.eye(10, dtype=np.float32)[idx])


def test_one_hot_rows_at_full_vocabulary():
    """qwen1.5's 151936-token vocabulary, without a V x V identity."""
    from repro.lower import one_hot_rows

    V = 151936
    idx = np.array([0, 151935, 42, 42])
    rows = one_hot_rows(idx, V)
    assert rows.shape == (4, V) and rows.dtype == np.float32
    np.testing.assert_array_equal(rows.argmax(axis=1), idx)
    np.testing.assert_array_equal(rows.sum(axis=1), np.ones(4, np.float32))


def test_oracle_relu_masks_fix_the_kink_side():
    """With each ReLU's own mask the oracle is unchanged; flipping one
    element moves exactly that fc1 gradient column."""
    from repro.launch.train import _dag_oracle_logits, _dag_oracle_loss
    from repro.lower import ReluSpec, one_hot_rows

    graph = _tiny_lm(n_layers=1)
    params = {k: jnp.asarray(v) for k, v in graph.init_params(seed=1).items()}
    rng = np.random.RandomState(8)
    V = graph.loss.classes
    x = one_hot_rows(rng.randint(0, V, graph.loss.batch), V)
    onehot = one_hot_rows(rng.randint(0, V, graph.loss.batch), V)
    relu = next(n for n in graph.nodes if isinstance(n.spec, ReluSpec))
    # the ReLU input: the oracle's forward cut just before the ReLU
    upto = dataclasses.replace(graph, nodes=graph.nodes[: graph.nodes.index(relu)])
    own = {relu.name: _dag_oracle_logits(upto, params, x) > 0}
    grad = jax.grad(lambda p, m: _dag_oracle_loss(graph, p, x, onehot, m))
    g0 = jax.grad(lambda p: _dag_oracle_loss(graph, p, x, onehot))(params)
    g1 = grad(params, own)
    for p in graph.param_shapes():
        np.testing.assert_allclose(g1[p], g0[p], rtol=1e-6, atol=1e-7, err_msg=p)
    # flip the element nearest its kink: the forward barely moves, and the
    # derivative change lands on that one fc1 gradient column
    upto_z = np.abs(np.asarray(_dag_oracle_logits(upto, params, x)))
    r, c = np.unravel_index(np.argmin(upto_z), upto_z.shape)
    flipped = {relu.name: own[relu.name].at[r, c].set(~own[relu.name][r, c])}
    g2 = grad(params, flipped)
    fc1 = next(n.param for n in graph.nodes if n.out_edge == relu.in_edge)
    moved = np.abs(np.asarray(g2[fc1]) - np.asarray(g1[fc1]))
    assert moved[:, c].max() > 100 * np.delete(moved, c, axis=1).max()


def test_lm_grad_check_uses_the_steps_own_relu_masks():
    """The check re-runs the step through run_pallas, whose ``relu_outputs``
    adds every ReLU's output edge without changing any other output, and
    reuses the executable the training steps compiled."""
    from repro.launch.train import _check_lm_grads
    from repro.lower import PlanCache, ReluSpec, lm_token_batches, run_pallas, train_graph
    from repro.lower.executors import _cache_stats

    graph = _tiny_lm()
    prog = lower_training_step(graph)
    V = graph.loss.classes
    batch_fn = lm_token_batches(np.random.RandomState(0), graph.batch, 6, V)
    cache = PlanCache()
    train_graph(graph, 1, batch_fn, program=prog, interpret=True, cache=cache,
                params=graph.init_params(seed=0), relu_outputs=True)
    traces = _cache_stats(cache)[2]
    res = _check_lm_grads(graph, prog, batch_fn, V, cache=cache, fuse=True, interpret=True)
    assert res["n_params"] == len(graph.param_shapes()) and res["worst_rel_err"] < 1e-4
    assert _cache_stats(cache)[2] == traces  # no new step was traced

    x, labels = batch_fn(1)
    inputs = {"x": x, "onehot": np.eye(V, dtype=np.float32)[labels], **graph.init_params()}
    outs = run_pallas(prog, inputs, cache=cache, interpret=True, relu_outputs=True)
    want = run_pallas(prog, inputs, cache=cache, interpret=True)
    relu_edges = {n.out_edge for n in graph.nodes if isinstance(n.spec, ReluSpec)}
    assert set(outs) == set(want) | relu_edges and not relu_edges & set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(outs[k]), np.asarray(want[k]), rtol=1e-5, atol=1e-6)
    for e in relu_edges:
        assert float(np.asarray(outs[e]).min()) == 0.0


def test_lm_grad_check_fails_a_planted_wrong_relu_mask(monkeypatch):
    """A step whose ReLUs zero everything under 0.1 std of their input puts
    elements well away from zero on the wrong side of the kink: the check
    must refuse it, whatever the logits and gradients would say."""
    from repro.launch.train import _check_lm_grads
    from repro.lower import PlanCache, ReluSpec, executors, lm_token_batches

    real = executors._plan_callable

    def planted(spec, pass_, interpret):
        if isinstance(spec, ReluSpec) and pass_ == "fwd":
            return lambda j: {"y": jnp.where(j["x"] > 0.1 * jnp.std(j["x"]), j["x"], 0.0)}
        return real(spec, pass_, interpret)

    monkeypatch.setattr(executors, "_plan_callable", planted)
    graph = _tiny_lm()
    prog = lower_training_step(graph)
    V = graph.loss.classes
    batch_fn = lm_token_batches(np.random.RandomState(0), graph.batch, 6, V)
    with pytest.raises(SystemExit, match="ReLU check FAILED"):
        _check_lm_grads(graph, prog, batch_fn, V, cache=PlanCache(), fuse=True, interpret=True)
