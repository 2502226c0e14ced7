"""Reduce a profiler trace (``.xplane.pb``) of the timed window to numbers.

Traced runs record device events only (see ``harness.run``). The window
starts where the harness's marker op (``MARK``, dispatched as the
window's first batch is asked for) starts on the device, and lasts the
window's length as the harness measured it, so it ends with the last
step's loss on the host and leaves out the parameters' return after it.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, clipped to the window, each named by its HLO
instruction (the text before `` = `` in the event name). From them:

* ``busy_s``: the union of the operation intervals, averaged over the
  devices that ran any; ``window_s``: the window's length;
* ``n_ops``: operations per device in the window;
* ``kernel_s(kernels)``: summed device time of the operations whose
  instruction name, without its ``.<n>`` suffix, is one of ``kernels``
  (a Pallas call is named after the jitted function that makes it, as
  ``streaming_matmul.38``);
* ``breakdown()``: the ten operations (by name) that took the most time,
  and the idle time split by where it falls: inside a step program's
  execution (an ``XLA Modules`` event), or between two, where the host
  is feeding, dispatching or reading back.
"""

from __future__ import annotations

import glob
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "jit_chipbench_window_mark"


@dataclass
class Summary:
    window_s: float
    busy_s: float
    n_ops: float
    ops: list = field(default_factory=list)  # (device, name, start_ns, end_ns)
    gaps: list = field(default_factory=list)  # (where, seconds)

    def kernel_s(self, kernels) -> float:
        n_dev = len({d for d, *_ in self.ops}) or 1
        return sum((e - s) for _, name, s, e in self.ops
                   if base_name(name) in kernels) * 1e-9 / n_dev

    def breakdown(self) -> dict:
        by_name: dict[str, float] = {}
        for _, name, s, e in self.ops:
            by_name[name] = by_name.get(name, 0.0) + (e - s) * 1e-9
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in sorted(self.gaps, key=lambda g: -g[1])]}


def op_name(event_name: str) -> str:
    """The HLO instruction's name, without its shapes and operands."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(op: str) -> str:
    """``streaming_matmul.38`` -> ``streaming_matmul``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a, b) -> float:
    """Total length of the overlap of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(data, window_s: float) -> Summary | None:
    """Reduce a ``ProfileData`` of a window ``window_s`` long; None when it
    holds no marker or no device operation in the window."""
    lines = [(plane.name, line) for plane in data.planes
             if plane.name.startswith(DEVICE_PREFIX) for line in plane.lines]
    modules = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for _, line in lines if line.name == MODULES_LINE for ev in line.events]
    marks = [s for s, _, n in modules if n.startswith(MARK)]
    if not marks:
        return None
    w0 = min(marks)
    w1 = w0 + window_s * 1e9
    ops = []
    for device, line in lines:
        if line.name != OPS_LINE:
            continue
        for ev in line.events:
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                ops.append((device, op_name(ev.name), s, e))
    if not ops:
        return None
    devices = sorted({d for d, *_ in ops})
    busy = 0.0
    for d in devices:
        busy += sum(e - s for s, e in _union([(s, e) for dd, _, s, e in ops if dd == d]))
    first = _union([(s, e) for d, _, s, e in ops if d == devices[0]])
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
    runs = _union([(max(s, w0), min(e, w1)) for s, e, n in modules
                   if not n.startswith(MARK) and e > w0 and s < w1])
    inside = _overlap(idle, runs)
    total_idle = sum(e - s for s, e in idle)
    gaps = [("inside a step program", inside * 1e-9),
            ("between step programs", (total_idle - inside) * 1e-9)]
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / len(devices),
                   n_ops=len(ops) / len(devices), ops=ops, gaps=gaps)


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    return Path(found[-1]) if found else None


def reduce_dir(trace_dir: Path, window_s: float) -> Summary | None:
    from jax._src.profiler import ProfileData

    path = find_xplane(trace_dir)
    return None if path is None else reduce(ProfileData.from_file(str(path)), window_s)


@dataclass
class RunView:
    """What a per-layer metric reader may read: the reduced trace (None
    when nothing ran on a device), the steps in the traced window, the
    graph, configuration and mix, the device kind, and the host seconds
    the feed spent encoding each batch."""

    summary: Summary | None
    steps: int
    graph: object
    cfg: dict
    mix: dict
    family: object
    kind: str
    encode_s: list
