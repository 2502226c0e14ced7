"""The numbers that decide ``correct`` for a training cell.

The program and the reference start from the same seeded weights ``w0``
and take the same first steps. Compared, each against a limit of its own
(``limits/<cell>.json``):

* ``loss_gap.<k>``: |program loss - reference loss| / reference loss at
  step k (k = 1, 2, 3);
* ``grad_gap``: the first gradient as the optimizer gets it, worked out
  from the program's state after one step ((w0 - w1) / lr for SGD, the
  momentum state v1 with momentum), against the reference's. Per leaf,
  the gap between the two norms over the larger of the reference leaf's
  norm and the median leaf's; the worst leaf counts;
* ``step_gap``: the same gap for the parameters' change after the steps
  (w3 - w0), over the leaves whose reference gradient is at least a
  thousandth of the median leaf's. The others (a bias under softmax, say)
  move by round-off alone and are left out by that rule, never by name;
* ``grad_gap.median``, ``step_gap.median``: the median leaf's gap, steady
  from seed to seed where the worst leaf's swings.

Norms are taken in float64 on the host.
"""

from __future__ import annotations

import math

import numpy as np

CHUNK = 1 << 24
EXCLUDE_BELOW = 1e-3  # of the median leaf's reference gradient norm


def norm(a, b=None, scale: float = 1.0) -> float:
    """Float64 norm of ``scale * (a - b)`` (or of ``a``), chunk by chunk."""
    a = np.asarray(a).reshape(-1)
    b = None if b is None else np.asarray(b).reshape(-1)
    total = 0.0
    for i in range(0, a.size, CHUNK):
        x = a[i:i + CHUNK].astype(np.float64)
        if b is not None:
            x -= b[i:i + CHUNK]
        total += float(np.dot(x, x))
    return math.sqrt(total) * abs(scale)


def _gaps(prog: dict, ref: dict, names) -> dict:
    med = float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in names}


def _worst_and_median(out: dict, name: str, gaps: dict) -> None:
    k = max(gaps, key=gaps.get)
    out[name], out[f"{name}_leaf"] = gaps[k], k
    out[f"{name}.median"] = float(np.median(list(gaps.values())))


def numbers(cfg: dict, w0: dict, prog: dict, ref: dict) -> dict:
    """``prog``: ``losses``, ``params`` after the last step, and either
    ``state1`` (the SGD params after step 1) or ``first_grad`` (the momentum
    state v1, or a stand-in's gradient); ``ref``: ``losses``,
    ``first_grad``, ``params``. Arrays may live on the host or a device."""
    t = cfg["train"]
    names = sorted(w0)
    out = {}
    for k, (lp, lr_) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_gap.{k}"] = abs(lp - lr_) / abs(lr_)
    if "first_grad" in prog:
        g_prog = {k: norm(prog["first_grad"][k]) for k in names}
    else:
        g_prog = {k: norm(w0[k], prog["state1"][k], 1.0 / t["lr"]) for k in names}
    g_ref = {k: norm(ref["first_grad"][k]) for k in names}
    _worst_and_median(out, "grad_gap", _gaps(g_prog, g_ref, names))
    med = float(np.median(list(g_ref.values())))
    moved = [k for k in names if g_ref[k] >= EXCLUDE_BELOW * med]
    d_prog = {k: norm(prog["params"][k], w0[k]) for k in moved}
    d_ref = {k: norm(ref["params"][k], w0[k]) for k in moved}
    _worst_and_median(out, "step_gap", _gaps(d_prog, d_ref, moved))
    out["left_out"] = sorted(set(names) - set(moved))
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the limited numbers. A
    number that is missing or not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = nums.get(name, float("nan"))
        checks[name] = {"value": v, "limit": limit}
        ok = ok and math.isfinite(v) and v <= limit
    return ok, checks
