"""Shared arithmetic of the per-layer metric readers (``metrics/*.py``).

Each reader takes a :class:`chipbench.trace_reduce.RunView` and returns a
number, or None when the trace holds nothing to read: a share of a peak or
of a roofline is never reported as 0 for want of data.
"""

from __future__ import annotations

from chipbench import work
from chipbench.peaks import peaks_for


def device_ops_per_step(run):
    s = run.summary
    return None if s is None else s.n_ops / run.steps


def idle_share(run):
    s = run.summary
    return None if s is None else 100.0 * (1.0 - s.busy_s / s.window_s)


def mfu(run):
    s = run.summary
    if s is None:
        return None
    flops = work.model_flops(run.graph) * run.steps
    return 100.0 * flops / s.window_s / peaks_for(run.kind)["flops_per_s"]


def kernel_roofline(run, patterns):
    """Least time of the passes the kernel is handed, over its device time."""
    s = run.summary
    if s is None:
        return None
    kernel_s = s.kernel_s(patterns)
    if kernel_s <= 0:
        return None
    least = work.roofline_s(work.streaming_passes(run.graph), peaks_for(run.kind))
    return 100.0 * least * run.steps / kernel_s


def encode_ms(run):
    return 1e3 * sum(run.encode_s) / len(run.encode_s) if run.encode_s else None
