"""The Pallas executor's node routes and its one graph walk.

Every node spec type the lowering registers needs an executor entry for
each of its fwd/dW/dX passes, so a type added without one fails here on
the CPU rather than mid-step. ``fuse=False`` walks the same segment walk
as the fuser, over a plan with no regions: every step of the schedule,
in order.
"""

import numpy as np
import pytest

from repro.lower import NetworkGraph, PlanCache, executors, lower_training_step, paper_cnn_graph
from repro.lower import rules
from repro.lower.fuse import step_schedule
from repro.lower.rules import SgdUpdateSpec, SoftmaxXentSpec

# the walk itself owns the loss gradient and the SGD update
NODE_TYPES = [t for t in rules._LOWERINGS if t not in (SoftmaxXentSpec, SgdUpdateSpec)]


@pytest.mark.parametrize("spec_type", NODE_TYPES, ids=lambda t: t.__name__)
def test_every_node_type_has_an_executor_entry(spec_type):
    entry = executors._ROUTES[spec_type]
    passes = [p for p in rules._LOWERINGS[spec_type] if p in ("fwd", "dw", "dx")]
    assert passes
    for pass_ in passes:
        assert callable(entry.get(pass_)), f"{spec_type.__name__} has no {pass_} wiring"
    assert callable(entry["plans"])


def _tiny_lm():
    from repro.models.config import ModelConfig

    cfg = ModelConfig(
        name="tiny", family="dense", n_layers=2, d_model=16,
        n_heads=2, n_kv_heads=2, head_dim=8, d_ff=32, vocab_size=13,
    )
    return NetworkGraph.from_model_config(cfg, batch=2, seq=6, lr=0.05)


def _inputs(graph, seed=0):
    rng = np.random.RandomState(seed)
    classes = graph.loss.classes
    first = graph.nodes[0]
    if len(first.in_shape) == 2:  # token rows: one-hot tokens
        x = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, graph.loss.batch)]
    else:
        x = rng.randn(graph.batch, *first.in_shape).astype(np.float32)
    onehot = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, graph.loss.batch)]
    return {graph.input_edge: x, graph.label_edge: onehot, **graph.init_params(seed=1)}


@pytest.mark.parametrize(
    "build",
    [_tiny_lm, lambda: paper_cnn_graph(batch=2, img=16, lr=0.05, momentum=0.9)],
    ids=["tiny_lm", "small_cnn"],
)
def test_unfused_walk_follows_the_step_schedule(build, monkeypatch):
    graph = build()
    program = lower_training_step(graph)
    walked = []
    real = executors._graph_step_local

    def spy(graph, j, plan, B, fusion, **kw):
        walked.append(fusion)
        return real(graph, j, plan, B, fusion, **kw)

    monkeypatch.setattr(executors, "_graph_step_local", spy)
    outs = executors.run_pallas(
        program, _inputs(graph), cache=PlanCache(), interpret=True, fuse=False
    )
    (fusion,) = walked
    assert fusion.n_regions == 0
    assert [s.step for s in fusion.segments] == step_schedule(graph)
    assert np.isfinite(np.asarray(outs[graph.loss_rows_edge])).all()
