"""One run of one cell: set-up, the timed window through ``train_graph``,
the reference check, and the result line.

Set-up builds the graph and lowers it (``lower_training_step``), makes a
``PlanCache``, draws the traffic pool and makes the weights on the device
in one jitted call from ``--seed``. It then drives that one program, cache
and state through the first three steps with ``train_graph`` itself (the
first compiles, or loads the step from the persistent compile cache):
those steps are what the reference checks. The window is one more
``train_graph`` call on the same program, cache and state, for the number
of steps that the warm steps show fills ``--seconds``. It starts when the
first batch is requested and ends when the last step's loss is on the
host: the entry of ``batch_fn(i)`` marks the start of step i, and the last
step ends ``walls[-1]`` after its start, so the one-time return of all
parameters as numpy at the end of the call is left out.
"""

from __future__ import annotations

import gc
import json
import math
import time
from pathlib import Path

import numpy as np

from chipbench import compare, reference
from chipbench.catalog import Catalog

#: steps of the first stretch, the ones the reference follows
CHECKED_STEPS = 3
#: the traced window is at most this long: traces are large, and tracing
#: slows the host
TRACE_SECONDS = 10.0
#: warm steps measured before the window's step count is fixed
TIMING_SECONDS = 1.0


class Feed:
    """``batch_fn`` for ``train_graph``: cycles the pre-drawn pool from
    ``start``, encodes each batch through the family's adapter (the
    program's encoder), and notes when each batch was asked for and how
    long encoding took."""

    def __init__(self, family, graph, items):
        self.family, self.graph, self.items = family, graph, items
        self.reset(0)

    def reset(self, start: int, mark=None):
        self.start, self.asked, self.encode_s = start, [], []
        self.mark = mark

    def __call__(self, step: int):
        t = time.perf_counter()
        self.asked.append(t)
        if self.mark is not None and step == 0:
            self.mark()  # puts the window's start on the device's timeline
        out = self.family.encode(self.items[(self.start + step) % len(self.items)],
                                 self.graph)
        self.encode_s.append(time.perf_counter() - t)
        return out


def init_params(family, cfg: dict, graph, seed: int) -> dict:
    """Seeded weights on the device, in one jitted call, as the
    configuration's ``train.init`` states: ``normal`` with ``init_std``, or
    Caffe's ``xavier`` filler (uniform on +-sqrt(3 / fan_in), fan_in all
    dims but the last); LayerNorm gamma 1 and beta 0; momentum 0."""
    import jax
    import jax.numpy as jnp

    shapes = graph.param_shapes()
    norms = family.norm_params(cfg)
    t = cfg["train"]
    key = jax.random.key(int(np.random.default_rng([int(seed) % 2**64, 0]).integers(2**31)))

    def leaf(k, name, shape):
        if name in norms:
            return jnp.stack([jnp.ones(shape[1:]), jnp.zeros(shape[1:])])
        if t["init"] == "normal":
            return t["init_std"] * jax.random.normal(k, shape, jnp.float32)
        if t["init"] == "xavier":
            bound = (3.0 / math.prod(shape[:-1])) ** 0.5
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        raise ValueError(f"unknown init {t['init']!r}")

    @jax.jit
    def make(key):
        out = {}
        for k, (name, shape) in zip(jax.random.split(key, len(shapes)), shapes.items()):
            out[name] = leaf(k, name, shape)
            if graph.momentum:
                out[f"v_{name}"] = jnp.zeros(shape, jnp.float32)
        return out

    return jax.block_until_ready(make(key))


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "used": min(chips, len(devs))}


def memory() -> dict:
    """The first device's allocator statistics (empty where JAX has none)."""
    import jax

    return jax.devices()[0].memory_stats() or {}


def peak_bytes(stats: dict) -> int | None:
    """Device memory held at the peak: the allocator's peak of buffers in
    use plus its peak reservation for the executables' temporaries, which
    ``peak_bytes_in_use`` leaves out (a TPU reserves a step's scratch apart
    from its buffers)."""
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def window_mark():
    """A one-element device op, dispatched as the window's first batch is
    asked for: its ``XLA Modules`` event opens the window on the device's
    timeline (``trace_reduce.MARK``). Traced runs only; compiled in set-up."""
    import jax
    import jax.numpy as jnp

    def chipbench_window_mark(x):
        return x + 1.0

    fn, x = jax.jit(chipbench_window_mark), jnp.zeros((), jnp.float32)
    jax.block_until_ready(fn(x))
    return lambda: fn(x)


def step_times(asked: list[float], last_wall: float) -> list[float]:
    """Seconds of every step of the window: ``batch_fn(i)`` was asked for
    at the start of step i, and the last step ended ``last_wall`` after
    its start (the program's own wall of that step), so the window ends
    when the last loss is on the host, before the parameters go back."""
    ends = asked[1:] + [asked[-1] + last_wall]
    return [e - s for s, e in zip(asked, ends)]


def end_to_end(unit: str, units_per_step: int, step_s: list[float], setup_s: float) -> dict:
    """The end-to-end values of a window: the rate is all the work of the
    window over all its time, the tail the 95th percentile of every step."""
    return {
        "setup_s": setup_s,
        f"{unit}_per_s": units_per_step * len(step_s) / sum(step_s),
        "step_ms_p95": float(np.percentile(np.asarray(step_s) * 1e3, 95)),
    }


class Bench:
    """What set-up builds once per process for one cell: the configuration,
    mix and family, the program's graph, its lowered step and its plan
    cache."""

    def __init__(self, cat: Catalog, workload: str):
        from repro.lower import PlanCache, lower_training_step

        self.cell = cat.cell(workload)
        self.cfg = cat.config(self.cell["config"])
        self.mix = cat.traffic(self.cell["traffic"])
        self.family = cat.family(self.cfg["family"])
        self.graph = self.family.build(self.cfg, self.mix)
        self.program = lower_training_step(self.graph)
        self.cache = PlanCache()
        self.names = list(self.graph.param_shapes())

    def train(self, feed, steps: int, params, start: int, mark=None) -> dict:
        """``steps`` steps of ``train_graph`` on this program and cache."""
        from repro.lower import train_graph

        feed.reset(start, mark)
        return train_graph(self.graph, steps, feed, program=self.program,
                           cache=self.cache, params=params)

    def first_steps(self, seed: int):
        """Seeded weights through the first steps: the program's results
        the reference checks, and the state and feed that go on."""
        items = self.family.pool(self.cfg, self.mix, seed)
        feed = Feed(self.family, self.graph, items)
        r1 = self.train(feed, 1, init_params(self.family, self.cfg, self.graph, seed), 0)
        mom = self.graph.momentum
        state1 = {k: r1["params"][f"v_{k}" if mom else k] for k in self.names}
        r3 = self.train(feed, CHECKED_STEPS - 1, r1["params"], 1)
        prog = {"losses": r1["losses"] + r3["losses"],
                "params": {k: r3["params"][k] for k in self.names}}
        if mom:
            prog["first_grad"] = state1
        else:
            prog["state1"] = state1
        return prog, r3, items, feed

    def reference(self, seed: int, items, *, precision: str = "highest",
                  fault: str | None = None):
        """The reference's first steps from the seeded weights ``w0``, and
        ``w0`` on the host."""
        import jax.numpy as jnp

        w0 = init_params(self.family, self.cfg, self.graph, seed)
        w0 = {k: np.asarray(w0[k]) for k in self.names}
        ref = reference.follow(self.family, self.cfg, self.mix,
                               {k: jnp.asarray(v) for k, v in w0.items()},
                               items[:CHECKED_STEPS], precision=precision, fault=fault)
        return ref, w0

    def check(self, seed: int, items, prog: dict) -> dict:
        """The comparison numbers of ``prog`` against the reference."""
        ref, w0 = self.reference(seed, items)
        return compare.numbers(self.cfg, w0, prog, ref)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, catalog: Catalog | None = None,
        on_chip: bool = True, trace_dir: Path | None = None,
        log=print) -> dict | None:
    """One run; the result object, or None when the cell's chips are missing.

    ``on_chip=False`` skips the look for a chip and the persistent compile
    cache, so that a test can drive the rest of a run on the CPU."""
    import jax
    import jax.numpy as jnp

    from chipbench.clock import CompileClock

    cat = catalog or Catalog()
    dev = device_info(cat.cell(workload)["chips"])
    log(f"device: {json.dumps(dev)}")
    chips = cat.cell(workload)["chips"]
    if on_chip and (dev["platform"] != "tpu" or dev["count"] < chips):
        log(f"chipbench needs {chips} TPU chip(s); JAX found "
            f"{dev['count']} {dev['platform']} device(s)")
        return None
    from repro.launch.train import use_compile_cache

    if on_chip:
        log(f"compile cache: {use_compile_cache()}")
        # every program, however quick to compile, so that only a cell's
        # first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    b = Bench(cat, workload)

    # the first steps: one program, cache and state, through train_graph
    prog, res, items, feed = b.first_steps(seed)
    state, start, walls = res["params"], CHECKED_STEPS, res["walls"]
    del res
    if sum(walls) < TIMING_SECONDS:
        n = max(1, math.ceil(TIMING_SECONDS / (sum(walls) / len(walls))))
        rt = b.train(feed, n, state, start)
        state, start, walls = rt["params"], start + n, rt["walls"]
        del rt
    warm_step_s = float(np.median(walls))
    window_goal = min(seconds, TRACE_SECONDS) if trace else seconds
    steps = max(1, round(window_goal / warm_step_s))
    state = jax.block_until_ready({k: jnp.asarray(v) for k, v in state.items()})
    profiling = trace_dir is not None and trace
    mark = window_mark() if profiling else None
    setup_s = time.perf_counter() - t_start
    clock_setup = clock.snapshot()
    log(f"set-up {setup_s:.3f} s; compile clock {json.dumps(clock_setup)}; "
        f"warm step {warm_step_s * 1e3:.2f} ms -> {steps} steps in the window")

    if profiling:
        # device events only: the host tracer, at any level that records
        # spans, also logs the runtime's chunked host transposes of each
        # input (over 100,000 events a step in the CNN cell), which slowed
        # that cell's traced steps fourfold. A marker op opens the window.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    res = b.train(feed, steps, state, start, mark)
    if profiling:
        jax.profiler.stop_trace()
    del state
    step_s = step_times(feed.asked, res["walls"][-1])
    window_s = sum(step_s)
    losses = res["losses"]
    del res
    in_window = clock.snapshot()["backend_compiles"] - clock_setup["backend_compiles"]
    stats = memory()
    peak = peak_bytes(stats)
    log(f"window: {steps} steps in {window_s:.6f} s; backend compiles in the "
        f"window: {in_window}; memory peak {peak} (peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')}, peak_bytes_reserved "
        f"{stats.get('peak_bytes_reserved')}); losses first {losses[0]!r}, "
        f"last {losses[-1]!r}, max {max(losses)!r}")
    log(f"window steps ms: {[round(t * 1e3, 1) for t in step_s]}")
    b.cache.clear()
    gc.collect()

    # the check, once the window has closed and the program's state is gone
    t_check = time.perf_counter()
    nums = b.check(seed, items, prog)
    correct, checks = compare.judge(nums, cat.limits(workload))
    log(f"check: {time.perf_counter() - t_check:.1f} s; program losses "
        f"{prog['losses']}; numbers {json.dumps(nums)}")

    result = {
        "correct": bool(correct) and in_window == 0,
        "attempted": steps,
        "failed": sum(not math.isfinite(v) for v in losses),
        "metrics": {},
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["used"], "memory_peak_bytes": peak},
    }
    if trace:
        from chipbench import trace_reduce

        summary = trace_reduce.reduce_dir(trace_dir, window_s) if profiling else None
        view = trace_reduce.RunView(
            summary=summary, steps=steps, graph=b.graph, cfg=b.cfg, mix=b.mix,
            family=b.family, kind=dev["kind"], encode_s=feed.encode_s[:steps])
        for m in cat.per_layer(workload):
            v = cat.reader(m["name"])(view)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            result["device"]["busy_s"] = summary.busy_s
            result["device"]["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
    else:
        values = end_to_end(b.family.UNIT, b.family.units_per_step(b.mix), step_s, setup_s)
        for m in cat.end_to_end(workload):
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result
