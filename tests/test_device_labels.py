"""The label one-hot built on the device: ``train_graph`` checks the label
ids on the host, hands them over as int32, and builds the float32 rows with
one jitted function, so the step's label input is bit for bit the array
:func:`one_hot_rows` gives while only 4 bytes a row cross to the device.
"""

import logging

import jax
import numpy as np
import pytest

from repro import obs
from repro.lower import (
    NetworkGraph,
    PlanCache,
    lm_token_batches,
    lower_training_step,
    one_hot_rows,
    paper_cnn_graph,
    run_pallas,
    train_graph,
)
from repro.lower import graph as graph_mod
from repro.lower.graph import device_one_hot_rows


def _tiny_lm(batch=2, seq=6):
    from repro.models.config import ModelConfig

    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=2, d_model=16, n_heads=2,
        n_kv_heads=2, head_dim=8, d_ff=32, vocab_size=13,
    )
    return NetworkGraph.from_model_config(cfg, batch=batch, seq=seq, lr=0.05)


GRAPHS = {
    "cnn": lambda: paper_cnn_graph(batch=4, img=8),
    "lm": _tiny_lm,
}


def _batches(graph, seed=0):
    """``batch_fn`` of (inputs, label ids) for a CNN or a token-row graph."""
    rng = np.random.RandomState(seed)
    if len(graph.input_shape) == 2:  # token rows: (seq, vocab)
        seq, vocab = graph.input_shape
        return lm_token_batches(rng, graph.batch, seq, vocab)

    def batch_fn(_i):
        x = rng.randn(graph.batch, *graph.input_shape).astype(np.float32)
        return x, rng.randint(0, graph.loss.classes, graph.batch)

    return batch_fn


@pytest.mark.parametrize("n_classes", [10, 1000, 151_936])
def test_device_rows_equal_host_rows_bit_for_bit(n_classes):
    rng = np.random.RandomState(n_classes)
    idx = np.concatenate([[0, n_classes - 1], rng.randint(0, n_classes, 14)])
    rows, ids = device_one_hot_rows(idx, n_classes)
    host = one_hot_rows(idx, n_classes)
    assert isinstance(rows, jax.Array)
    assert rows.dtype == np.float32 and rows.shape == host.shape
    got = np.asarray(rows)
    np.testing.assert_array_equal(got.view(np.uint32), host.view(np.uint32))
    assert ids.dtype == np.int32 and ids.nbytes == 4 * idx.size
    np.testing.assert_array_equal(ids, idx)


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_node"])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_train_graph_matches_a_loop_over_host_labels(name, fuse):
    """Losses and every parameter after 3 steps are bit-identical to a
    ``run_pallas`` loop fed :func:`one_hot_rows` labels from the host."""
    graph = GRAPHS[name]()
    prog = lower_training_step(graph)
    cache = PlanCache()
    params0 = graph.init_params(seed=1)
    res = train_graph(graph, 3, _batches(graph), program=prog, cache=cache,
                      params=params0, fuse=fuse)

    batch_fn, params, losses = _batches(graph), dict(params0), []
    for i in range(3):
        x, labels = batch_fn(i)
        inputs = {graph.input_edge: np.asarray(x, np.float32),
                  graph.label_edge: one_hot_rows(labels, graph.loss.classes),
                  **params}
        outs = run_pallas(prog, inputs, cache=cache, fuse=fuse)
        losses.append(float(np.mean(np.asarray(outs[graph.loss_rows_edge],
                                               np.float64))))
        for p in graph.param_shapes():
            params[p] = outs[f"{p}_new"]
            if graph.momentum:
                params[f"v_{p}"] = outs[f"v_{p}_new"]

    assert res["losses"] == losses
    assert set(res["params"]) == set(params)
    for k, v in params.items():
        np.testing.assert_array_equal(
            res["params"][k].view(np.uint32),
            np.asarray(v, np.float32).view(np.uint32), err_msg=k)


def test_a_second_call_at_the_same_shapes_compiles_nothing(caplog):
    graph = GRAPHS["lm"]()
    prog, cache = lower_training_step(graph), PlanCache()
    res = train_graph(graph, 1, _batches(graph, 0), program=prog, cache=cache)
    size = graph_mod._one_hot_jit()._cache_size()
    with jax.log_compiles(), caplog.at_level(logging.DEBUG):
        train_graph(graph, 2, _batches(graph, 1), program=prog, cache=cache,
                    params=res["params"])
    assert graph_mod._one_hot_jit()._cache_size() == size
    compiles = [r.getMessage() for r in caplog.records
                if "ompil" in r.getMessage()]
    assert compiles == []


@pytest.mark.parametrize("bad", ["negative", "classes"])
def test_an_id_outside_the_classes_is_refused(bad):
    n = 10
    idx = np.array([3, -1 if bad == "negative" else n, 2])
    with pytest.raises(ValueError, match=r"\[0, 10\)"):
        device_one_hot_rows(idx, n)


@pytest.mark.parametrize("bad", ["negative", "classes"])
def test_train_graph_refuses_an_id_outside_the_classes(bad):
    graph = paper_cnn_graph(batch=2, img=8)
    n = graph.loss.classes
    x = np.zeros((graph.batch, *graph.input_shape), np.float32)
    labels = np.array([0, -1 if bad == "negative" else n])
    with pytest.raises(ValueError, match="label ids"):
        train_graph(graph, 1, lambda _i: (x, labels), cache=PlanCache())


def test_lm_steps_copy_label_ids_not_label_rows():
    """``h2d_bytes`` counts 4 bytes a label row, and never the one-hot."""
    graph = GRAPHS["lm"]()
    col = obs.TraceCollector()
    with obs.use_collector(col):
        train_graph(graph, 2, _batches(graph), cache=PlanCache())
    steps = [s for s in col.host_spans() if s[0] == "step"]
    rows = graph.batch * graph.input_shape[0]
    x_bytes = rows * graph.input_shape[1] * 4
    assert steps[1][4]["h2d_bytes"] == x_bytes + rows * 4
