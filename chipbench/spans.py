"""The host spans of ``train_graph`` beside the device trace of one cell.

    python chipbench/spans.py --workload <cell> --seed <n>

The benchmark's traced run (``run.py --trace 1``) installs no span
collector yet; this script is how the spans are read on the chip until it
does. It makes the set-up that ``harness.run`` makes (without the
reference check), then, on the same program, cache and state:

1. four windows of the same number of steps, with no collector and with
   one in turn (off, on, off, on): the window step times of each side,
   and the cost of one span on this host;
2. one window under the profiler, device events only as in the traced
   run, with a collector installed:

   * the clock check: the spans are on the wall clock, and the trace's
     events count from the profile's start on it (``profile_start_ns``);
     the offset of the window mark's device event from the host stamp
     taken just before it was dispatched (by which the spans are then
     aligned), and whether every step program's device interval lies
     inside its step's ``dispatch``...``wait`` spans;
   * the idle time between step programs, split by the leaf span of
     ``train_graph`` that covers it (``between_gaps``);
   * the accepted per-layer metrics of the cell, read from this window,
     and the per-step values of the spans (``span_metrics``);
   * the stats of one kernel event, where a named scope would show.

The last line of standard output is one JSON object. Off a TPU it exits 1.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace_reduce  # noqa: E402

#: the leaf spans of one ``train_graph`` step, in order
LEAVES = ("batch", "labels", "dispatch", "wait", "readback", "loss", "commit")
#: per-step milliseconds of these leaf spans, summed
SPAN_MS = {
    "host_loss_ms": ("loss",),
    "readback_ms": ("readback",),
    "feed_ms": ("batch", "labels"),
    "dispatch_ms": ("dispatch",),
}
#: a step program may start or end this close outside its step's spans
CLOCK_SLACK_NS = 1_000_000


def _minus(a, b):
    """Sorted disjoint intervals ``a`` less the sorted disjoint ``b``."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append([s, bs])
            s = max(s, be)
        if e > s:
            out.append([s, e])
    return out


def between_gaps(idle, runs, spans, w0, w1) -> dict[str, float]:
    """Seconds of the idle time between step programs (``idle`` less
    ``runs``, both sorted disjoint ns intervals) that each leaf span name
    covers, clipped to the window ``[w0, w1)``, and the rest under ``no
    span``. ``spans`` are ``(name, t0_ns, t1_ns, step, args)``. The values
    sum to the idle time between step programs."""
    between = _minus(idle, runs)
    out = {}
    for name in LEAVES:
        mine = trace_reduce._union([(max(s, w0), min(e, w1)) for n, s, e, *_ in spans
                                    if n == name and min(e, w1) > max(s, w0)])
        out[f"between step programs: {name}"] = trace_reduce._overlap(between, mine) * 1e-9
    total = sum(e - s for s, e in between) * 1e-9
    out["between step programs: no span"] = total - sum(out.values())
    return out


def span_metrics(spans) -> dict[str, float] | None:
    """Per ``step`` span: the ``SPAN_MS`` values, and the megabytes handed
    from the host (``host_to_device_mb``); None without step spans."""
    steps = [a for n, *_, a in spans if n == "step"]
    if not steps:
        return None
    out = {metric: 1e-6 * sum(e - s for n, s, e, *_ in spans if n in names) / len(steps)
           for metric, names in SPAN_MS.items()}
    out["host_to_device_mb"] = 1e-6 * sum(a["h2d_bytes"] for a in steps) / len(steps)
    return out


def clock_check(stamp_ns: int, mark_ns: int, programs, spans) -> dict:
    """The mark's device start less the host stamp before its dispatch
    (both on the trace's time base), and how the step programs' device
    intervals (``(start, end)`` ns) sit in their step's
    ``dispatch``...``wait``: each is matched to the step whose interval,
    widened by ``CLOCK_SLACK_NS``, holds it."""
    steps = {}
    for n, s, e, step, _ in spans:
        if n in ("dispatch", "wait"):
            lo, hi = steps.get(step, (s, e))
            steps[step] = (min(lo, s), max(hi, e))
    inside, lead, tail = 0, [], []
    for ps, pe in programs:
        for lo, hi in steps.values():
            if lo - CLOCK_SLACK_NS <= ps and pe <= hi + CLOCK_SLACK_NS:
                inside += 1
                lead.append((ps - lo) * 1e-6)
                tail.append((hi - pe) * 1e-6)
                break
    return {"mark_offset_ms": (mark_ns - stamp_ns) * 1e-6,
            "programs": len(programs), "programs_inside": inside,
            "least_lead_ms": min(lead, default=None),
            "least_tail_ms": min(tail, default=None)}


def device_intervals(data, summary, window_s: float):
    """From a ``ProfileData`` and its ``Summary``: the window's start (the
    mark's device start) and end, the first device's idle intervals in it,
    and its step programs' intervals (all ns)."""
    modules = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for plane in data.planes if plane.name.startswith(trace_reduce.DEVICE_PREFIX)
               for line in plane.lines if line.name == trace_reduce.MODULES_LINE
               for ev in line.events]
    w0 = min(s for s, _, n in modules if n.startswith(trace_reduce.MARK))
    w1 = w0 + window_s * 1e9
    first = min(d for d, *_ in summary.ops)
    busy = trace_reduce._union([(s, e) for d, _, s, e in summary.ops if d == first])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
    programs = sorted((s, e) for s, e, n in modules if not n.startswith(trace_reduce.MARK)
                      and e > w0 and s < w1)
    return w0, w1, idle, programs


def profile_start_ns(data) -> int | None:
    """Wall-clock ns at which the profile started, which the xplane's
    event times count from (stat ``profile_start_time`` of its ``Task
    Environment`` plane); None where the trace has no such stat."""
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = {str(k): v for k, v in plane.stats}
            if "profile_start_time" in stats:
                return int(stats["profile_start_time"])
    return None


def kernel_stats(data, kernel: str = "streaming_matmul") -> dict:
    """The stats of the first device op named ``<kernel>.<n>``: where the
    op's ``jax.named_scope`` shows in the trace."""
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                if trace_reduce.base_name(trace_reduce.op_name(ev.name)) == kernel:
                    return {str(k): str(v)[:300] for k, v in ev.stats}
    return {}


def span_cost_us(n: int = 20000) -> float:
    """Microseconds one host span (open and close) costs on this host."""
    from repro.obs import TraceCollector

    col = TraceCollector()
    t = time.perf_counter()
    with col.host_span("step", step=0):
        for _ in range(n):
            with col.host_span("leaf"):
                pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax._src.profiler import ProfileData

    from chipbench import harness
    from chipbench.catalog import Catalog
    from repro.launch.train import use_compile_cache
    from repro.obs import TraceCollector, use_collector
    from repro.obs.trace import clock_ns

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cat = Catalog()
    dev = harness.device_info(cat.cell(args.workload)["chips"])
    if dev["platform"] != "tpu":
        log(f"needs a TPU; JAX found {dev}")
        return 1
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    b = harness.Bench(cat, args.workload)
    items = b.family.pool(b.cfg, b.mix, args.seed)
    feed = harness.Feed(b.family, b.graph, items)
    res = b.train(feed, harness.CHECKED_STEPS,
                  harness.init_params(b.family, b.cfg, b.graph, args.seed), 0)
    state, start = res["params"], harness.CHECKED_STEPS
    steps = max(3, round(harness.TRACE_SECONDS / statistics.median(res["walls"][1:])))
    del res
    log(f"{steps} steps a window")

    def window(col=None, mark=None):
        nonlocal state, start
        on_device = jax.block_until_ready({k: jnp.asarray(v) for k, v in state.items()})
        with use_collector(col):
            r = b.train(feed, steps, on_device, start, mark)
        state, start = r["params"], start + steps
        return harness.step_times(feed.asked, r["walls"][-1])

    step_ms = {"off": [], "on": []}
    for side in ("off", "on", "off", "on"):
        step_ms[side] += [t * 1e3 for t in window(TraceCollector() if side == "on" else None)]
        log(f"{side}: median {statistics.median(step_ms[side][-steps:]):.3f} ms")
    med = {k: statistics.median(v) for k, v in step_ms.items()}
    out = {"workload": args.workload, "seed": args.seed, "device": dev, "steps": steps,
           "step_ms": step_ms, "median_step_ms": med,
           "overhead_pct": 100.0 * (med["on"] / med["off"] - 1.0),
           "span_cost_us": span_cost_us()}

    def profiled(col=None, mark=None, hlo=False):
        """A window under the profiler, device events only; its step
        seconds and its trace."""
        trace_dir = ROOT / ".chipbench" / "spans" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = hlo
        try:
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            step_s = window(col, mark)
            jax.profiler.stop_trace()
            return step_s, ProfileData.from_file(str(trace_reduce.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    mark_fn, stamps = harness.window_mark(), []

    def mark():
        stamps.append(clock_ns())
        return mark_fn()

    col = TraceCollector()
    step_s, data = profiled(col, mark)
    window_s = sum(step_s)
    summary = trace_reduce.reduce(data, window_s)
    origin = profile_start_ns(data)
    if summary is None or origin is None:
        log(f"nothing to read: summary {summary is not None}, profile start {origin}")
    else:
        # the xplane's events count from the profile's start on the wall
        # clock, but the device's times carried onto it may sit a
        # millisecond off the host's: the spans are aligned so that the
        # stamp taken as the mark was dispatched falls where the mark starts
        w0, w1, idle, programs = device_intervals(data, summary, window_s)
        stamp = stamps[0] - origin
        spans = [(n, s - origin + w0 - stamp, e - origin + w0 - stamp, st, a)
                 for n, s, e, st, a in col.host_spans()]
        gaps = dict(summary.gaps) | between_gaps(idle, programs, spans, w0, w1)
        view = trace_reduce.RunView(
            summary=summary, steps=steps, graph=b.graph, cfg=b.cfg, mix=b.mix,
            family=b.family, kind=dev["kind"], encode_s=feed.encode_s[:steps])
        out |= {
            "profile_start_ns": origin,
            "clock": clock_check(stamp, w0, programs, spans),
            "busy_s": summary.busy_s, "window_s": summary.window_s,
            "idle_gaps": gaps,
            "named_share": 1.0 - (gaps["between step programs: no span"]
                                  / gaps["between step programs"]),
            "accepted_metrics": {m["name"]: cat.reader(m["name"])(view)
                                 for m in cat.per_layer(args.workload)},
            "span_metrics": span_metrics(spans),
        }
    # where the walk's named scopes show: with the traced run's options,
    # and with the HLO proto recorded too
    out["kernel_stats"] = {"hlo_proto_off": kernel_stats(data),
                           "hlo_proto_on": kernel_stats(profiled(hlo=True)[1])}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
