"""The host spans laid over the device trace (``chipbench/spans.py``), on
the hand-made trace of ``test_chipbench_trace`` and hand-made spans."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest
from test_chipbench_trace import HAND_MADE

from chipbench import spans, trace_reduce

ROOT = Path(__file__).resolve().parents[2]

# two steps of train_graph's leaf spans (ns, on the device trace's clock)
# over the hand-made trace, whose idle time between step programs in its
# 10 us window [500, 10500) is [500, 1000) [4000, 6000) [7000, 10500)
HAND_SPANS = [
    ("step", 400, 6600, 0, {"step": 0, "h2d_bytes": 3_000_000}),
    ("batch", 400, 800, 0, {}),  # starts before the window
    ("labels", 800, 900, 0, {}),
    ("dispatch", 900, 1100, 0, {}),
    ("wait", 1100, 4200, 0, {}),
    ("readback", 4200, 4500, 0, {}),
    ("loss", 4500, 6500, 0, {}),
    ("commit", 6500, 6600, 0, {}),  # the device is busy throughout
    ("step", 7000, 11000, 1, {"step": 1, "h2d_bytes": 1_000_000}),
    ("batch", 7000, 8000, 1, {}),
    ("labels", 8000, 8500, 1, {}),
    ("dispatch", 10000, 11000, 1, {}),  # ends after the window
]


@pytest.fixture(scope="module")
def hand_made():
    from jax._src.profiler import ProfileData

    data = ProfileData.from_text_proto(HAND_MADE)
    return data, trace_reduce.reduce(data, 10e-6)


def test_device_intervals_of_the_hand_made_trace(hand_made):
    w0, w1, idle, programs = spans.device_intervals(*hand_made, 10e-6)
    assert (w0, w1) == (500, 10500)
    assert idle == [[500, 1000], [3500, 6000], [7000, 10500]]
    assert programs == [(1000, 4000)]


def test_between_gaps_split_the_between_programs_idle_time(hand_made):
    data, summary = hand_made
    w0, w1, idle, programs = spans.device_intervals(data, summary, 10e-6)
    gaps = spans.between_gaps(idle, programs, HAND_SPANS, w0, w1)
    ns = {k.removeprefix("between step programs: "): v * 1e9 for k, v in gaps.items()}
    assert ns == pytest.approx({
        "batch": 300 + 1000, "labels": 100 + 500, "dispatch": 100 + 500,
        "wait": 200, "readback": 300, "loss": 1500, "commit": 0,
        "no span": 1500,  # [8500, 10000)
    })
    assert sum(gaps.values()) == pytest.approx(dict(summary.gaps)["between step programs"])


def test_minus_of_interval_lists():
    assert spans._minus([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert spans._minus([[4, 10], [20, 30]], [[2, 6]]) == [[6, 10], [20, 30]]
    assert spans._minus([[0, 5]], []) == [[0, 5]]


def test_span_metrics_per_step():
    m = spans.span_metrics(HAND_SPANS)
    assert m == pytest.approx({
        "host_loss_ms": 2000e-6 / 2, "readback_ms": 300e-6 / 2,
        "feed_ms": (400 + 100 + 1000 + 500) * 1e-6 / 2,
        "dispatch_ms": (200 + 1000) * 1e-6 / 2, "host_to_device_mb": 2.0,
    })
    assert spans.span_metrics([]) is None
    assert spans.span_metrics([s for s in HAND_SPANS if s[0] != "step"]) is None


def test_clock_check_matches_programs_to_their_steps():
    far = (20_000_000, 20_001_000)  # inside no step's dispatch..wait
    c = spans.clock_check(400, 500, [(1000, 4000), far], HAND_SPANS)
    assert c["mark_offset_ms"] == pytest.approx(100e-6)
    assert (c["programs"], c["programs_inside"]) == (2, 1)
    assert c["least_lead_ms"] == pytest.approx(100e-6)
    assert c["least_tail_ms"] == pytest.approx(200e-6)


def test_the_script_exits_non_zero_and_prints_nothing_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/spans.py", "--workload", "cnn_googlenet.b128",
         "--seed", "3"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1 and p.stdout == ""


def test_profile_start_of_the_recorded_trace():
    from jax._src.profiler import ProfileData

    data = ProfileData.from_file(str(Path(__file__).parent / "fixtures"
                                     / "streaming_matmul_x3.xplane.pb"))
    assert spans.profile_start_ns(data) == 1792312834952589332
    assert spans.profile_start_ns(ProfileData.from_text_proto(HAND_MADE)) is None
    # the marker module opens the window; three step modules run in it
    s = trace_reduce.reduce(data, 0.01)
    w0, w1, _, programs = spans.device_intervals(data, s, 0.01)
    assert (w0, w1) == (42_226_922, 52_226_922)
    assert programs == [(44_784_991, 44_784_991 + 8_797), (48_030_484, 48_030_484 + 8_912),
                        (51_350_854, 51_350_854 + 8_763)]
