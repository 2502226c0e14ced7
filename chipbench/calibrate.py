"""Readings that the limits of ``correct`` are set from, on the chip.

    python chipbench/calibrate.py --workload <cell> --seeds 12 --controls 3

All in one process, so the step compiles once. For each seed the program
runs its first steps as a run does and is compared with the reference:
the lower readings. For the first ``--controls`` seeds the control (the
reference computed at "high", on a TPU ``Precision.HIGH``, in the
program's place) and each planted fault (``half``: half of the batch left
out, the mean taken over the rest; ``token``: a token, or a label, altered
where it is produced) are compared with the reference too: the upper
readings. A step that returns its state unchanged reads 1 on
``step_gap`` by construction and is not run. One JSON line per reading on stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

FAULTS = ("half", "token")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--cpu", action="store_true", help="allow a run off a TPU")
    args = ap.parse_args()

    import jax

    from chipbench import compare, harness
    from chipbench.catalog import Catalog
    from repro.launch.train import use_compile_cache

    if jax.devices()[0].platform != "tpu" and not args.cpu:
        print("calibrate needs a TPU", file=sys.stderr)
        return 3
    use_compile_cache()
    b = harness.Bench(Catalog(), args.workload)

    def emit(seed, kind, nums, t0):
        line = {"workload": args.workload, "seed": seed, "kind": kind,
                "seconds": round(time.perf_counter() - t0, 1), **nums}
        print(json.dumps(line), flush=True)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        prog, res, items, _ = b.first_steps(seed)
        del res
        emit(seed, "program", b.check(seed, items, prog), t0)
        if i >= args.controls:
            continue
        ref, w0 = b.reference(seed, items)
        for kind, kw in [("control", {"precision": "high"})] + [
                (f"fault:{f}", {"fault": f}) for f in FAULTS]:
            t0 = time.perf_counter()
            stand_in, _ = b.reference(seed, items, **kw)
            emit(seed, kind, compare.numbers(b.cfg, w0, stand_in, ref), t0)
            del stand_in
    return 0


if __name__ == "__main__":
    sys.exit(main())
