"""The unified telemetry layer: counters, merged traces, reports.

The load-bearing property is the tentpole's acceptance criterion: counter
totals recorded by the executors must equal the program's *closed-form*
counts (``NtxProgram.n_offloads`` / ``n_commands`` / ``dma_bytes``)
exactly — the counters are the program's own arithmetic, not a parallel
estimate. On top of that: registry mechanics (scoping, snapshot/restore,
merge, zero-overhead-off), the per-step JSONL schema, the plan-cache and
mesh-link instrumentation, the merged Perfetto trace's lanes and flow
events, and the shared BENCH ``schema_version`` envelope.
"""

import json

import numpy as np
import pytest

from repro import obs
from repro.lower import (
    lower_training_step,
    paper_cnn_graph,
    run_reference,
    run_timing,
    shard_training_step,
    train_graph,
)
from repro.obs.counters import block_scope, program_totals

jax = pytest.importorskip("jax")

from repro.lower import executors  # noqa: E402
from repro.lower.executors import PlanCache, run_pallas  # noqa: E402


def _graph_and_inputs(batch=2, img=8, seed=0):
    graph = paper_cnn_graph(batch=batch, img=img, lr=0.05, momentum=0.9)
    prog = lower_training_step(graph, n_clusters=4)
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, img, img, 3).astype(np.float32)
    labels = rng.randint(0, graph.loss.classes, batch)
    onehot = np.eye(graph.loss.classes, dtype=np.float32)[labels]
    inputs = {graph.input_edge: x, graph.label_edge: onehot,
              **graph.init_params(seed=1)}
    return graph, prog, inputs


# ---------------------------------------------------------------------------
# Registry mechanics
# ---------------------------------------------------------------------------


def test_registry_scoping_and_totals():
    reg = obs.CounterRegistry()
    with reg.scope("step0"):
        with reg.scope("c1", "fwd"):
            reg.inc("offloads", 3)
            reg.inc("dma_bytes", 100)
        with reg.scope("c2", "fwd"):
            reg.inc("offloads", 2)
    reg.inc("offloads")  # root scope
    assert reg.get("step0/c1/fwd/offloads") == 3
    assert reg.get("step0/c2/fwd/offloads") == 2
    assert reg.total("offloads") == 6
    assert reg.total("offloads", prefix="step0/") == 5
    assert reg.totals("step0/") == {"offloads": 5, "dma_bytes": 100}
    assert reg.tree()["step0"]["c1"]["fwd"]["offloads"] == 3


def test_registry_prefixes_do_not_collide():
    # step1 must not swallow step10 (the trailing-separator contract).
    reg = obs.CounterRegistry()
    with reg.scope("step1"):
        reg.inc("offloads", 1)
    with reg.scope("step10"):
        reg.inc("offloads", 100)
    assert reg.total("offloads", prefix="step1/") == 1


def test_registry_disabled_records_nothing():
    reg = obs.CounterRegistry(enabled=False)
    with reg.scope("a"):
        reg.inc("x", 5)
    assert len(reg) == 0
    obs.record_program(reg, object())  # must not even touch the program


def test_registry_empty_is_still_truthy():
    # `if reg:` at an instrument site must mean "telemetry on", never
    # "has already counted something".
    assert bool(obs.CounterRegistry())
    assert len(obs.CounterRegistry()) == 0


def test_use_registry_installs_and_restores():
    assert obs.get_active() is None
    reg = obs.CounterRegistry()
    with obs.use_registry(reg):
        assert obs.get_active() is reg
    assert obs.get_active() is None


def test_snapshot_restore_merge_roundtrip():
    reg = obs.CounterRegistry()
    reg.inc("a/x", 2)
    reg.inc("y", 1.5)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap  # checkpoint-extras safe
    reg.inc("a/x", 10)
    reg.restore(snap)
    assert reg.get("a/x") == 2
    other = obs.CounterRegistry()
    other.inc("a/x", 3)
    reg.merge(other)
    assert reg.get("a/x") == 5
    reg.merge(snap)
    assert reg.get("y") == 3.0


def test_block_scope_mapping():
    assert block_scope("c1:fwd:conv") == ("c1", "fwd")
    assert block_scope("fc:dw:matmul") == ("fc", "dw")
    assert block_scope("spill:act1") == ("tcdm", "spill")
    assert block_scope("fill:act1") == ("tcdm", "fill")
    assert block_scope("allreduce:update:fc:upd[0]") == ("mesh", "allreduce")
    assert block_scope("allgather:w_c1[1]") == ("mesh", "allgather")
    assert block_scope("") == ("untagged",)


# ---------------------------------------------------------------------------
# Executor counters == closed-form program counts (the acceptance check)
# ---------------------------------------------------------------------------


def test_run_reference_counters_match_closed_form():
    graph, prog, inputs = _graph_and_inputs()
    reg = obs.CounterRegistry()
    with obs.use_registry(reg):
        run_reference(prog, inputs)
    want = program_totals(prog)
    got = reg.totals()
    for leaf, v in want.items():
        assert got.get(leaf, 0) == v, leaf
    assert got["macs"] > 0
    assert want["offloads"] == prog.n_offloads
    assert want["dma_bytes"] == prog.dma_bytes


def test_run_timing_records_program_and_schedule():
    _, prog, _ = _graph_and_inputs()
    reg = obs.CounterRegistry()
    with obs.use_registry(reg):
        result = run_timing(prog, n_clusters=4)
    assert reg.total("commands") == prog.n_commands
    assert reg.get("timing/scheduled_programs") == 1
    assert reg.get("timing/total_cycles") == result.total_cycles
    assert reg.get("timing/exec_cycles") == result.exec_cycles
    assert reg.get("timing/exec_cycles") > 0


def test_run_pallas_counters_and_plan_cache():
    graph, prog, inputs = _graph_and_inputs()
    cache = PlanCache()
    reg = obs.CounterRegistry()
    with obs.use_registry(reg):
        with reg.scope("cold"):
            run_pallas(prog, inputs, cache=cache)
        with reg.scope("warm"):
            run_pallas(prog, inputs, cache=cache)
    for pfx in ("cold/", "warm/"):
        assert reg.total("commands", prefix=pfx) == prog.n_commands
        assert reg.total("offloads", prefix=pfx) == prog.n_offloads
    assert reg.get("cold/plan_cache/misses") > 0
    assert reg.get("warm/plan_cache/misses", 0) == 0
    assert reg.get("warm/plan_cache/hits") > 0
    assert reg.get("warm/plan_cache/retraces", 0) == 0


def test_zero_overhead_when_disabled_records_nothing_globally():
    graph, prog, inputs = _graph_and_inputs()
    assert obs.get_active() is None
    run_reference(prog, inputs)  # no registry installed: must not blow up


def test_train_graph_jsonl_matches_closed_form(tmp_path):
    graph, prog, _ = _graph_and_inputs(batch=2, img=8)
    rng = np.random.RandomState(0)
    eyec = np.eye(graph.loss.classes, dtype=np.float32)
    x = rng.randn(2, 8, 8, 3).astype(np.float32)
    labels = rng.randint(0, graph.loss.classes, 2)
    path = tmp_path / "metrics.jsonl"
    reg = obs.CounterRegistry()
    res = train_graph(graph, 2, lambda _i: (x, labels), program=prog,
                      backend="reference", registry=reg,
                      metrics_path=str(path))
    assert res["registry"] is reg
    recs = obs.read_jsonl(path)
    assert [r["step"] for r in recs] == [0, 1]
    want = program_totals(prog)
    for r in recs:
        assert r["schema_version"] == obs.SCHEMA_VERSION
        assert r["counters"]["offloads"] == want["offloads"]
        assert r["counters"]["commands"] == want["commands"]
        assert r["counters"]["dma_bytes"] == want["dma_bytes"]
        assert r["wall_s"] > 0 and "loss" in r
    # per-step scopes sum to steps x closed form
    assert reg.total("commands") == 2 * prog.n_commands


# ---------------------------------------------------------------------------
# Mesh-link counters
# ---------------------------------------------------------------------------


def test_time_mesh_step_link_counters_match_schedule():
    from repro.runtime.mesh import MeshInterconnect, time_mesh_step

    graph = paper_cnn_graph(batch=4, img=8)
    reg = obs.CounterRegistry()
    with obs.use_registry(reg):
        sharded = shard_training_step(graph, mesh_shape=(2, 2), n_clusters=4)
        time_mesh_step(sharded, n_clusters=4)
    upd = MeshInterconnect(2, 2).systolic_update(sharded.allreduce_bytes)
    assert reg.total("link_hops") == len(upd.transfers)
    assert reg.total("link_bytes") == sum(
        st.transfer.num_bytes for st in upd.transfers
    )
    assert reg.get("shard/programs") == 1
    assert reg.get("shard/hmcs") == 4
    assert reg.get("shard/allreduce_bytes") == sharded.allreduce_bytes


# ---------------------------------------------------------------------------
# Merged Perfetto trace
# ---------------------------------------------------------------------------


def test_merged_trace_has_all_lanes_and_flows(tmp_path):
    graph = paper_cnn_graph(batch=4, img=8)
    col = obs.TraceCollector()
    with obs.use_collector(col):
        sharded = shard_training_step(graph, mesh_shape=(2, 2), n_clusters=4)
        result, upd = col.add_mesh_step(sharded, n_clusters=4)
    cats = {e.get("cat") for e in col.events}
    assert {"exec", "dma", "link", "lowering", "flow"} <= cats
    phs = {e["ph"] for e in col.events}
    assert {"X", "s", "f"} <= phs  # flow starts + finishes present
    pids = {e["pid"] for e in col.events}
    assert {"hmc0", "mesh", "host"} <= pids
    # exec spans cover every non-elided block exactly once per cluster share
    path = tmp_path / "trace.json"
    col.save(path)
    doc = json.loads(path.read_text())
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ns"
    # every flow id has exactly one start and one finish
    starts = [e["id"] for e in col.events if e["ph"] == "s"]
    fins = [e["id"] for e in col.events if e["ph"] == "f"]
    assert sorted(starts) == sorted(fins)


def test_dispatch_spans_recorded_by_pallas_executor():
    graph, prog, inputs = _graph_and_inputs()
    # fused default: the collector does not change the step that runs, so
    # it is one call of the cached step plan and no span is recorded from
    # inside its trace
    cache = PlanCache()
    col = obs.TraceCollector()
    with obs.use_collector(col):
        run_pallas(prog, inputs, cache=cache)
    steps = [p for p in cache._plans.values() if p.key[0] == "train_step"]
    assert [p.calls for p in steps] == [1]
    assert col.spans == [] and col.events == []
    # the per-node escape hatch dispatches plan by plan from the host, and
    # records one dispatch span per plan call
    cache = PlanCache()
    col = obs.TraceCollector()
    with obs.use_collector(col):
        run_pallas(prog, inputs, cache=cache, fuse=False)
    n_calls = sum(p.calls for p in cache._plans.values())
    assert n_calls > 1
    assert [e["cat"] for e in col.events] == ["dispatch"] * n_calls


def test_mesh_program_on_one_device_runs_its_step_plan_when_traced():
    graph = paper_cnn_graph(batch=4, img=8)
    sharded = shard_training_step(graph, mesh_shape=(2, 2), n_clusters=4)
    _, _, inputs = _graph_and_inputs(batch=4)
    cache = PlanCache()
    col = obs.TraceCollector()
    with obs.use_collector(col):
        run_pallas(sharded.program, inputs, cache=cache)
    steps = [p for p in cache._plans.values() if p.key[0] == "train_step"]
    assert [p.calls for p in steps] == [1]
    assert col.spans == []


_SHARD_MAP_TRACED = """
import numpy as np
from repro import obs
from repro.lower import PlanCache, paper_cnn_graph, run_pallas, shard_training_step
graph = paper_cnn_graph(batch=4, img=8)
sharded = shard_training_step(graph, mesh_shape=(2, 2), n_clusters=4)
rng = np.random.RandomState(0)
inputs = {graph.input_edge: rng.randn(4, 8, 8, 3).astype(np.float32),
          graph.label_edge: np.eye(10, dtype=np.float32)[rng.randint(0, 10, 4)],
          **graph.init_params(seed=1)}
plain = run_pallas(sharded.program, inputs, cache=PlanCache())
col = obs.TraceCollector()
with obs.use_collector(col):
    traced = run_pallas(sharded.program, inputs, cache=PlanCache())
assert col.spans == [], [s.name for s in col.spans]
assert all(np.array_equal(np.asarray(plain[k]), np.asarray(traced[k])) for k in plain)
print("ok")
"""


def test_shard_map_step_records_no_span_at_trace_time():
    """On four (fake) devices the mesh program runs under ``shard_map``:
    a collector records nothing from inside its body and changes no
    output."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _SHARD_MAP_TRACED], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]


LEAVES = ["batch", "labels", "dispatch", "wait", "readback", "loss", "commit"]


def _cnn_batches(graph, seed=0):
    rng = np.random.RandomState(seed)
    shape = (graph.batch,) + tuple(graph.input_shape)

    def batch_fn(_i):
        return (rng.randn(*shape).astype(np.float32),
                rng.randint(0, graph.loss.classes, graph.batch))

    return batch_fn


def test_train_graph_records_step_spans_and_counters():
    import time

    # a step long enough that the spans' own bookkeeping (microseconds)
    # is a small share of it
    graph = paper_cnn_graph(batch=8, img=64)
    params = graph.init_params(seed=1)
    col = obs.TraceCollector()
    t_start = time.time_ns()
    with obs.use_collector(col):
        res = train_graph(graph, 2, _cnn_batches(graph), params=params,
                          cache=PlanCache())
    t_end = time.time_ns()
    spans = col.host_spans()
    steps = [s for s in spans if s[0] == "step"]
    assert [s[3] for s in steps] == [0, 1]
    by_id = {s.id: s for s in col.spans}
    for i, (_, s0, s1, _, args) in enumerate(steps):
        # wall-clock nanoseconds, the clock of the profiler's events
        assert t_start <= s0 < s1 <= t_end
        leaves = [s for s in col.spans if s.parent is not None
                  and by_id[s.parent].name == "step" and s.step == i]
        leaves.sort(key=lambda s: s.id)
        assert [s.name for s in leaves] == LEAVES
        assert all(a.t1_ns <= b.t0_ns for a, b in zip(leaves, leaves[1:]))
        covered = sum(s.t1_ns - s.t0_ns for s in leaves)
        assert covered >= 0.95 * (s1 - s0)
        assert args["step"] == i
        assert (args["plan_retraces"] > 0) == (i == 0)  # the first step compiles
    x_bytes = graph.batch * int(np.prod(graph.input_shape)) * 4
    # the label ids cross as int32; their one-hot rows are built on the device
    ids_bytes = graph.batch * 4
    param_bytes = sum(v.nbytes for v in params.values())
    # params go to the device once; after that they stay there
    assert steps[0][4]["h2d_bytes"] == x_bytes + ids_bytes + param_bytes
    assert steps[1][4]["h2d_bytes"] == x_bytes + ids_bytes
    # the host reads back one float32 loss per row, never the logits
    assert all(s[4]["d2h_bytes"] == graph.batch * 4 for s in steps)
    assert len(res["losses"]) == 2
    # the chrome export rebases the host lanes to zero
    host = [e for e in col.to_chrome_trace()["traceEvents"] if e["pid"] == "host"]
    assert min(e["ts"] for e in host) == 0.0


@pytest.mark.parametrize("n_classes", [10, 1000])
def test_train_graph_reads_back_the_loss_rows_whatever_the_classes(n_classes):
    """``d2h_bytes`` counts the loss rows: a float32 per row, the same
    at 10 classes and at 1000."""
    graph = paper_cnn_graph(batch=2, img=8, n_classes=n_classes)
    col = obs.TraceCollector()
    with obs.use_collector(col):
        train_graph(graph, 1, _cnn_batches(graph), cache=PlanCache())
    (step,) = [s for s in col.host_spans() if s[0] == "step"]
    assert step[4]["d2h_bytes"] == graph.batch * 4


def test_train_graph_spans_add_no_plan_cache_miss_or_trace():
    graph = paper_cnn_graph(batch=2, img=8)
    program = lower_training_step(graph, n_clusters=4)
    cache = PlanCache()
    batches = _cnn_batches(graph)
    res = train_graph(graph, 1, batches, program=program, cache=cache)
    hits, misses, traces, calls = executors._cache_stats(cache)
    col = obs.TraceCollector()
    with obs.use_collector(col):
        train_graph(graph, 1, batches, program=program, cache=cache,
                    params=res["params"])
    h2, m2, t2, c2 = executors._cache_stats(cache)
    assert (m2, t2) == (misses, traces)
    assert (h2, c2) == (hits + 1, calls + 1)  # one call of the step plan
    (step,) = [s for s in col.host_spans() if s[0] == "step"]
    assert step[4]["plan_retraces"] == 0


def test_host_spans_nest_and_inherit_the_step():
    col = obs.TraceCollector()
    with col.host_span("step", step=3) as args:
        with col.host_span("inner"):
            pass
        args["n"] = 1
    with col.host_span("free"):
        pass
    assert [(n, st, a) for n, _, _, st, a in col.host_spans()] == [
        ("step", 3, {"n": 1}), ("inner", 3, {}), ("free", None, {})]
    outer, inner, free = sorted(col.spans, key=lambda s: s.id)
    assert inner.parent == outer.id and outer.parent is None and free.parent is None
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns


def test_block_spans_cover_commands():
    from repro.obs.trace import block_spans

    _, prog, _ = _graph_and_inputs()
    result = run_timing(prog, n_clusters=4, engine="event")
    spans = list(block_spans(prog, result, 4))
    assert sum(n for *_x, n in spans) == prog.n_commands
    for _c, _tag, e0, e1, _d0, _d1, _n in spans:
        assert e1 >= e0 >= 0


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------


def test_hotspot_table_renders_sections():
    reg = obs.CounterRegistry()
    with reg.scope("c1", "fwd"):
        reg.inc("busy_cycles", 5_000_000)
        reg.inc("dma_bytes", 123)
    txt = obs.format_hotspots(reg, k=3)
    assert "by cycles" in txt and "c1/fwd" in txt and "5.00M" in txt
    assert "by DMA bytes" in txt
    assert "by link bytes" not in txt  # no link traffic recorded


def test_bench_json_writer_stamps_schema_version(tmp_path):
    p = tmp_path / "BENCH_x.json"
    obs.write_bench_json({"summary": {"a": 1}, "schema_version": 999}, p)
    doc = json.loads(p.read_text())
    assert doc["schema_version"] == obs.SCHEMA_VERSION
    assert doc["summary"] == {"a": 1}


def test_offload_bench_envelope_single_writer(tmp_path):
    p = tmp_path / "BENCH_offload.json"
    results = {"a": {"wall_s": 1.5, "summary": {}},
               "b": {"wall_s": 0.5, "summary": {}}}
    obs.write_offload_bench(results, p)
    doc = json.loads(p.read_text())
    assert doc["total_wall_s"] == 2.0
    assert doc["schema_version"] == obs.SCHEMA_VERSION
    assert set(doc["benchmarks"]) == {"a", "b"}


def test_metrics_writer_coerces_arrays(tmp_path):
    path = tmp_path / "m.jsonl"
    with obs.MetricsWriter(path) as w:
        w.write({"step": 0, "metrics": {"ce": np.float32(1.25)}})
    recs = obs.read_jsonl(path)
    assert recs[0]["metrics"]["ce"] == 1.25
