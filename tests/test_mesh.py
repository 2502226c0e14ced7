"""Mesh-of-HMCs data parallelism: sharded programs + the link layer.

The contract under test is the §4.9 scaling story made executable:

  * ``shard_training_step`` splits a whole-train-step program across the
    mesh **bit-identically** — ``run_reference`` on the sharded program
    equals the unsharded step with ``assert_array_equal``, not a tolerance
    (batch splits and output-chunk reduce-scatter splits never move an
    accumulator rounding).
  * The allreduce epilogue is explicit: reduce-scatter chunks own every
    ``d_<param>``, update chunks follow, and the weight allgather carries
    ``(n-1)`` chunk transfers of link traffic.
  * The link layer reproduces eqs. (14)-(15) exactly on square meshes,
    serializes congested links, and pins its §4.9 constants to
    ``benchmarks/ntx_model.py``.
  * ``time_mesh_step`` + ``ntx_model.mesh`` agree on parallel efficiency
    within 1% with the paper's >= 95% bar cleared (full 4-size sweep in
    the slow lane; one size in tier-1).

The shard_map gradient oracle against ``jax.grad`` at 1/4/16 fake devices
lives in ``tests/distributed`` (fresh subprocesses own the device count).
"""

import numpy as np
import pytest

from repro.lower import (
    NS_DESIGN,
    lower_training_step,
    paper_cnn_graph,
    parse_mesh,
    reshard_training_step,
    run_reference,
    shard_training_step,
)
from repro.lower.mesh import ALL_HMCS
from repro.runtime.mesh import (
    HOP_LATENCY,
    LINK_BW,
    LinkTransfer,
    MeshInterconnect,
    expected_update_time,
    time_mesh_step,
)


def _inputs(graph, seed=0):
    rng = np.random.RandomState(seed)
    b, img = graph.batch, graph.input_shape[0]
    x = rng.randn(b, img, img, 3).astype(np.float32)
    labels = rng.randint(0, graph.loss.classes, b)
    onehot = np.eye(graph.loss.classes, dtype=np.float32)[labels]
    return {"x": x, "onehot": onehot, **graph.init_params(seed=seed + 1)}


# ---------------------------------------------------------------------------
# Bit-identity: the sharded program IS the unsharded step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("design,momentum", [
    (None, 0.9),  # NTX + momentum
    (None, 0.0),  # NTX plain SGD
    (NS_DESIGN, 0.9),  # NS: every block carries driver reps
])
@pytest.mark.parametrize("mesh", [(2, 2), (4, 2), (1, 1)])
def test_sharded_bit_identical_to_unsharded(design, momentum, mesh):
    graph = paper_cnn_graph(batch=8, img=8, momentum=momentum)
    kw = {} if design is None else {"design": design}
    prog = lower_training_step(graph, **kw)
    sh = shard_training_step(graph, mesh_shape=mesh, program=prog, **kw)
    inputs = _inputs(graph)
    want = run_reference(prog, inputs)
    got = run_reference(sh.program, inputs)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_sharded_spilled_program_bit_identical():
    """Spill/fill blits split across shards without touching semantics."""
    graph = paper_cnn_graph(batch=8, img=16)
    prog = lower_training_step(graph, n_clusters=1)  # tiny budget -> spills
    assert prog.meta["spilled"]
    sh = shard_training_step(graph, mesh_shape=(2, 2), program=prog,
                             n_clusters=1)
    inputs = _inputs(graph, seed=3)
    want = run_reference(prog, inputs)
    got = run_reference(sh.program, inputs)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


# ---------------------------------------------------------------------------
# The allreduce epilogue and shard assignment
# ---------------------------------------------------------------------------


def test_allreduce_epilogue_structure():
    graph = paper_cnn_graph(batch=8, img=8, momentum=0.9)
    sh = shard_training_step(graph, mesh_shape=(2, 2))
    n = sh.n_hmcs
    epi = sh.epilogue_blocks()
    assert epi, "no allreduce epilogue emitted"
    reduced = {w for _, b in epi if b.tag.startswith("allreduce:reduce")
               for w in b.writes}
    assert reduced == {f"d_{p}" for p in graph.param_shapes()}
    updated = {w for _, b in epi if b.tag.startswith("allreduce:update")
               for w in b.writes}
    for p in graph.param_shapes():
        assert f"{p}_new" in updated and f"v_{p}_new" in updated
    gathers = [(h, b) for h, b in epi if b.tag.startswith("allgather:")]
    for p, shape in graph.param_shapes().items():
        size = int(np.prod(shape))
        mine = [(h, b) for h, b in gathers if b.reads == (f"{p}_new",)]
        # one chunk per HMC (parameters smaller than the mesh: one per elem)
        assert len(mine) == min(n, size)
        assert sorted(h for h, _ in mine) == list(range(len(mine)))
        # each broadcast carries its chunk to the n-1 other replicas
        total = sum(b.dma_bytes_out for _, b in mine)
        assert total == pytest.approx(size * 4 * (n - 1))


def test_shard_programs_partition_the_combined_stream():
    graph = paper_cnn_graph(batch=8, img=8)
    sh = shard_training_step(graph, mesh_shape=(2, 2))
    owned = [h for h in sh.hmc_of_block if h != ALL_HMCS]
    assert set(owned) == set(range(sh.n_hmcs))
    per_shard = [sh.shard_program(h) for h in range(sh.n_hmcs)]
    replicated = sum(1 for h in sh.hmc_of_block if h == ALL_HMCS)
    assert sum(len(p.blocks) for p in per_shard) == (
        len(sh.program.blocks) + replicated * (sh.n_hmcs - 1)
    )
    # compute commands are conserved: the combined stream carries exactly
    # the unsharded commands plus the allgather identity copies
    gather_cmds = sum(b.n_commands for _, b in sh.epilogue_blocks()
                      if b.tag.startswith("allgather:"))
    assert sh.program.busy_cycles == (
        sh.base_program.busy_cycles
        + sum(b.busy_cycles for _, b in sh.epilogue_blocks()
              if b.tag.startswith("allgather:"))
    )
    assert gather_cmds > 0


def test_mesh_validation_errors():
    graph = paper_cnn_graph(batch=6, img=8)
    with pytest.raises(ValueError, match="does not divide"):
        shard_training_step(graph, mesh_shape=(2, 2))
    with pytest.raises(ValueError, match="not 'RxC'"):
        parse_mesh("2by2")
    assert parse_mesh("2x4") == (2, 4) and parse_mesh((4, 4)) == (4, 4)


# ---------------------------------------------------------------------------
# run_pallas routes (single-device tier-1 coverage; multi-device in slow)
# ---------------------------------------------------------------------------


def test_run_pallas_mesh_routes_match_reference():
    from repro.lower import PlanCache, run_pallas

    graph = paper_cnn_graph(batch=4, img=8, momentum=0.9)
    prog = lower_training_step(graph)
    inputs = _inputs(graph, seed=5)
    want = run_reference(prog, inputs)
    # 1x1: the shard_map path over a single-device mesh
    sh1 = shard_training_step(graph, mesh_shape=(1, 1), program=prog)
    got1 = run_pallas(sh1.program, inputs, cache=PlanCache())
    # 2x2 on one device: the graceful single-device fallback walk
    sh4 = shard_training_step(graph, mesh_shape=(2, 2), program=prog)
    got4 = run_pallas(sh4.program, inputs, cache=PlanCache())
    for got in (got1, got4):
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k]), want[k], rtol=2e-3, atol=1e-5, err_msg=k
            )


# ---------------------------------------------------------------------------
# The link layer (repro.runtime.mesh)
# ---------------------------------------------------------------------------


def test_link_constants_pinned_to_analytical_model():
    M = pytest.importorskip("benchmarks.ntx_model")
    assert LINK_BW == M.LINK_BW
    assert HOP_LATENCY == M.HOP_LATENCY


@pytest.mark.parametrize("side", [2, 4, 8, 16])
def test_systolic_update_matches_eq15(side):
    net = MeshInterconnect(side, side)
    for w in (1e6, 300e6):
        want = 4.0 * (w / LINK_BW + side * HOP_LATENCY)
        assert net.update_time(w) == pytest.approx(want, rel=1e-12)
        assert expected_update_time(w, side, side) == pytest.approx(want)
    # congestion-free on the line embedding
    assert net.systolic_update(300e6).congestion_time == 0.0


def test_single_cube_has_no_update():
    assert MeshInterconnect(1, 1).update_time(300e6) == 0.0


def test_rectangular_mesh_update_matches_closed_form():
    # two passes per non-degenerate axis, each paying its own hop count
    for rows, cols in ((4, 2), (2, 4), (1, 4), (4, 1)):
        net = MeshInterconnect(rows, cols)
        want = sum(2.0 * (300e6 / LINK_BW + ax * HOP_LATENCY)
                   for ax in (rows, cols) if ax > 1)
        assert net.update_time(300e6) == pytest.approx(want, rel=1e-12)
        assert expected_update_time(300e6, rows, cols) == pytest.approx(want)


def test_link_congestion_serializes():
    net = MeshInterconnect(2, 2)
    link = ((0, 0), (0, 1))
    s = net.schedule([LinkTransfer(link, LINK_BW), LinkTransfer(link, LINK_BW)])
    # two 1-second transfers on one link: the second queues a full second
    assert s.transfers[1].queued == pytest.approx(1.0 + HOP_LATENCY)
    assert s.makespan == pytest.approx(2.0 + 2 * HOP_LATENCY)
    # distinct links run concurrently
    s2 = net.schedule([LinkTransfer(((0, 0), (0, 1)), LINK_BW),
                       LinkTransfer(((1, 0), (1, 1)), LINK_BW)])
    assert s2.makespan == pytest.approx(1.0 + HOP_LATENCY)
    assert s2.congestion_time == 0.0


def test_ring_allreduce_wrap_latency():
    # a 1x4 snake ring's wrap edge is a 3-hop store-and-forward path: the
    # ring must run past the congestion-free single-hop floor by the
    # wrap's extra hops, and every step must still serialize cleanly
    net = MeshInterconnect(1, 4)
    n = net.n_hmcs
    step_t = 4e6 / n / LINK_BW + HOP_LATENCY
    floor = 2 * (n - 1) * step_t
    sched = net.ring_allreduce(4e6)
    assert sched.makespan == pytest.approx(floor + 2 * step_t)
    # a square mesh's snake ring closes on a real link: exactly the floor
    sq = MeshInterconnect(2, 2).ring_allreduce(4e6)
    assert sq.makespan == pytest.approx(2 * 3 * step_t)
    assert sq.congestion_time == 0.0
    # two rings sharing the mesh congest: re-run the same transfers twice
    doubled = net.schedule(
        [t for s in (sched, sched) for t in
         (x.transfer for x in s.transfers)]
    )
    assert doubled.congestion_time > 0.0


def test_schedule_rejects_bogus_links():
    net = MeshInterconnect(2, 2)
    with pytest.raises(ValueError, match="nearest-neighbour"):
        net.schedule([LinkTransfer(((0, 0), (1, 1)), 1.0)])
    with pytest.raises(ValueError, match="outside"):
        net.schedule([LinkTransfer(((0, 0), (0, 2)), 1.0)])


# ---------------------------------------------------------------------------
# Executed + timed mesh steps vs the analytical model
# ---------------------------------------------------------------------------


def test_time_mesh_step_composition():
    graph = paper_cnn_graph(batch=8, img=8)
    sh = shard_training_step(graph, mesh_shape=(2, 2))
    tm = time_mesh_step(sh)
    assert tm.t_update == pytest.approx(
        expected_update_time(sh.allreduce_bytes, 2, 2)
    )
    assert tm.t_step == pytest.approx(tm.t_shard + tm.t_update)
    assert tm.speedup == pytest.approx(tm.t_single / tm.t_step)
    assert tm.parallel_eff == pytest.approx(tm.speedup / 4)
    assert tm.shard_cycles > 0 and tm.single_cycles > tm.shard_cycles


def test_mesh_efficiency_executed_one_size():
    """Tier-1 slice of the acceptance gate: one executed mesh size must
    clear 95% parallel efficiency within 1% of ``ntx_model.mesh``."""
    M = pytest.importorskip("benchmarks.ntx_model")
    workloads = pytest.importorskip("benchmarks.workloads")

    graph = workloads.network_graph("googlenet", batch=256)
    sh = shard_training_step(graph, mesh_shape=(2, 2))
    tm = time_mesh_step(sh)
    mod = M.mesh(2, 256, t_image=tm.t_image, weight_bytes=sh.allreduce_bytes)
    assert tm.parallel_eff >= 0.95
    assert abs(tm.parallel_eff - mod.parallel_eff) / mod.parallel_eff < 0.01


@pytest.mark.slow
def test_mesh_efficiency_executed_full_sweep():
    """The full >= 4-size acceptance sweep (same code path as
    ``benchmarks/mesh_bench.py`` and the CI BENCH_mesh.json gate)."""
    mesh_bench = pytest.importorskip("benchmarks.mesh_bench")

    rows, summary = mesh_bench.mesh_executed_sweep()
    assert summary["four_or_more_sizes"]
    assert summary["parallel_eff_above_95pct"], summary
    assert summary["within_1pct_of_model"], summary


# ---------------------------------------------------------------------------
# 2D sharding: pipeline rows x tensor/data columns
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("design,momentum", [
    (None, 0.9),  # NTX + momentum
    (None, 0.0),  # NTX plain SGD
    (NS_DESIGN, 0.9),  # NS: every block carries driver reps
])
@pytest.mark.parametrize("mesh", [(2, 2), (2, 4), (4, 2)])
def test_2d_bit_identical_to_unsharded(design, momentum, mesh):
    """The signature guarantee extends to 2D: tensor-channel splits,
    pipeline send/recv copies and row-scoped reduce/update/gather never
    move a flop or an accumulator rounding."""
    graph = paper_cnn_graph(batch=8, img=8, momentum=momentum)
    kw = {} if design is None else {"design": design}
    prog = lower_training_step(graph, **kw)
    sh = shard_training_step(graph, mesh_shape=mesh, program=prog,
                             shard="2d", **kw)
    assert sh.shard == "2d"
    inputs = _inputs(graph)
    want = run_reference(prog, inputs)
    got = run_reference(sh.program, inputs)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_2d_spilled_program_bit_identical():
    graph = paper_cnn_graph(batch=8, img=16)
    prog = lower_training_step(graph, n_clusters=1)  # tiny budget -> spills
    assert prog.meta["spilled"]
    sh = shard_training_step(graph, mesh_shape=(2, 2), program=prog,
                             n_clusters=1, shard="2d")
    inputs = _inputs(graph, seed=3)
    want = run_reference(prog, inputs)
    got = run_reference(sh.program, inputs)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_2d_pipeline_structure():
    """Stages partition the layer sequence in order, every stage boundary
    gets a send/recv pair (activation down, gradient up), and the weight
    epilogue is row-scoped (stage params live only on their row)."""
    graph = paper_cnn_graph(batch=8, img=8, momentum=0.9)
    sh = shard_training_step(graph, mesh_shape=(2, 2), shard="2d")
    meta = sh.program.meta["mesh"]
    pmeta = meta["pipeline"]
    rows, cols = sh.mesh_shape
    assert pmeta["n_stages"] == rows
    # stages are a contiguous, order-preserving partition of the layers
    flat = [nd for stage in pmeta["stages"] for nd in stage]
    assert flat == [nd.name for nd in graph.nodes]
    assert meta["row_owners"] == [[0, 1], [2, 3]]
    # each of the rows-1 boundaries ships the activation down and its
    # gradient back up as explicit identity-copy blocks
    xfers = pmeta["xfers"]
    assert len(xfers) == 2 * (rows - 1)
    dirs = {(x["src"], x["dst"]) for x in xfers}
    assert dirs == {(0, 1), (1, 0)}
    tags = [b.tag for b in sh.program.blocks]
    for x in xfers:
        sends = [t for t in tags if t.startswith(f"send:{x['region']}[")]
        recvs = [t for t in tags if t.startswith(f"recv:{x['region']}[")]
        assert sends and len(sends) == len(recvs), x
    # row-scoped epilogue: every reduce/update/gather block is owned by a
    # cube on its parameter's home row
    row_of = {h: r for r, ro in enumerate(meta["row_owners"]) for h in ro}
    stage_of = {nd: r for r, stage in enumerate(pmeta["stages"]) for nd in stage}
    param_rows = pmeta["param_rows"]
    for h, b in sh.epilogue_blocks():
        if b.tag.startswith(("allreduce:", "allgather:")):
            assert h != ALL_HMCS
            name = b.writes[0] if b.writes else b.reads[0]
            base = name.removeprefix("d_").removeprefix("v_")
            base = base.removesuffix("_new")
            assert row_of[h] == param_rows[base], (b.tag, h)
    # tensor-sharded layers (conv/matmul/bias) really fan across columns
    assert any(t.startswith("tpgather:") for t in tags)
    assert all(r in set(param_rows.values()) for r in range(rows))
    assert stage_of  # partition non-empty


def test_2d_traffic_conservation():
    """Compute commands are conserved: the combined 2D stream is exactly
    the unsharded step plus the identity-copy communication blocks
    (tpgather/allgather/send/recv)."""
    graph = paper_cnn_graph(batch=8, img=8, momentum=0.9)
    sh = shard_training_step(graph, mesh_shape=(2, 2), shard="2d")
    comm = sum(
        b.busy_cycles for b in sh.program.blocks
        if b.tag.startswith(("tpgather:", "allgather:", "send:", "recv:"))
    )
    assert comm > 0
    assert sh.program.busy_cycles == sh.base_program.busy_cycles + comm


def test_2d_reshard_tensor_group_bit_identical():
    """Survivability x 2D: killing one cube of a tensor group re-chunks
    that pipeline stage over the row's survivors, bit-identically."""
    graph = paper_cnn_graph(batch=8, img=8, momentum=0.9)
    prog = lower_training_step(graph)
    sh = shard_training_step(graph, mesh_shape=(2, 2), program=prog,
                             shard="2d")
    degraded = reshard_training_step(sh, 1)  # row 0 keeps only cube 0
    assert degraded.shard == "2d"
    assert degraded.program.meta["mesh"]["row_owners"] == [[0], [2, 3]]
    inputs = _inputs(graph, seed=7)
    want = run_reference(prog, inputs)
    got = run_reference(degraded.program, inputs)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    # a second loss in the other row still re-shards
    twice = reshard_training_step(degraded, 3)
    got2 = run_reference(twice.program, inputs)
    for k in want:
        np.testing.assert_array_equal(got2[k], want[k], err_msg=k)
    # losing a whole pipeline row is unrecoverable by re-chunking
    with pytest.raises(ValueError, match="lost every cube"):
        reshard_training_step(twice, 0)


def test_2d_validation_errors():
    graph = paper_cnn_graph(batch=8, img=8)
    with pytest.raises(ValueError, match="shard must be"):
        shard_training_step(graph, mesh_shape=(2, 2), shard="3d")
    # more pipeline rows than layers with compute cannot balance
    with pytest.raises(ValueError, match="pipeline"):
        shard_training_step(graph, mesh_shape=(8, 1), shard="2d")


def test_run_pallas_2d_routes_match_reference():
    from repro.lower import PlanCache, run_pallas

    graph = paper_cnn_graph(batch=4, img=8, momentum=0.9)
    prog = lower_training_step(graph)
    inputs = _inputs(graph, seed=5)
    want = run_reference(prog, inputs)
    # 2x2 on one device: the graceful single-device fallback walk
    sh = shard_training_step(graph, mesh_shape=(2, 2), program=prog,
                             shard="2d")
    got = run_pallas(sh.program, inputs, cache=PlanCache())
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k]), want[k], rtol=2e-3, atol=1e-5, err_msg=k
        )


def test_time_mesh_step_2d_composition():
    graph = paper_cnn_graph(batch=8, img=8)
    sh = shard_training_step(graph, mesh_shape=(2, 2), shard="2d")
    tm = time_mesh_step(sh)  # dispatches to the 2D model
    assert tm.mesh_shape == (2, 2)
    assert len(tm.row_times) == 2 and all(t > 0 for t in tm.row_times)
    assert tm.n_micro == sh.program.meta["mesh"]["pipeline"]["n_micro"]
    assert tm.t_step == pytest.approx(
        max(tm.t_compute, tm.t_boundary) + tm.t_update
    )
    assert 0.0 <= tm.bubble_frac < 1.0
    assert tm.speedup == pytest.approx(tm.t_single / tm.t_step)
    assert tm.parallel_eff == pytest.approx(tm.speedup / 4)
    s = tm.summary()
    for key in ("mesh", "n_micro", "bubble_frac", "parallel_eff",
                "row_times_ms", "t_boundary_ms"):
        assert key in s


def test_2d_efficiency_executed_one_size():
    """Tier-1 slice of the 2D acceptance gate: GoogLeNet (too big for one
    HMC at bench scale) on a 2x2 must clear the 80% efficiency floor."""
    workloads = pytest.importorskip("benchmarks.workloads")

    graph = workloads.network_graph("googlenet", batch=256)
    sh = shard_training_step(graph, mesh_shape=(2, 2), shard="2d")
    tm = time_mesh_step(sh)
    assert tm.parallel_eff >= 0.80
    assert tm.bubble_frac <= 0.25


def test_compiled_mesh_program_refuses_too_few_devices():
    """Compiled kernels never walk a mesh program on one device: with fewer
    jax devices than HMCs, run_pallas raises before building any plan."""
    import jax

    from repro.lower import PlanCache, run_pallas

    graph = paper_cnn_graph(batch=4, img=8, momentum=0.9)
    sharded = shard_training_step(graph, mesh_shape=(2, 2))
    assert jax.device_count() < sharded.n_hmcs
    cache = PlanCache()
    with pytest.raises(RuntimeError, match="needs 4 jax devices"):
        run_pallas(sharded.program, _inputs(graph, seed=5), interpret=False, cache=cache)
    assert len(cache) == 0
