"""The trace reduction, on a hand-made trace and on a recorded one."""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace_reduce

FIXTURES = Path(__file__).resolve().parent / "fixtures"

# on the device (ns from the line start 1000): the marker module at 500,
# a step module [1000, 4000); ops: the kernel [1000, 3000), a fusion
# [2500, 3500) overlapping it, the kernel again [6000, 7000), and an op
# wholly after a 10 us window
HAND_MADE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: -500000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%streaming_matmul.38 = f32[8,128] custom-call(f32[8,256] %a, f32[256,128] %b)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = f32[8,128] fusion(f32[8,128] %streaming_matmul.38)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_chipbench_window_mark(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_counted(456)" } }
}
'''


def test_hand_made_trace_reduces_to_hand_counts():
    from jax._src.profiler import ProfileData

    # the window: from the marker (500 ns), 10 us as the harness measured
    s = trace_reduce.reduce(ProfileData.from_text_proto(HAND_MADE), 10e-6)
    assert s.window_s == pytest.approx(10e-6)
    # union of [1000,3000) [2500,3500) [6000,7000) = 2500 + 1000 ns
    assert s.busy_s == pytest.approx(3.5e-6)
    assert s.n_ops == 3  # the op after the window is not counted
    # by instruction name: the fusion that reads the kernel's output is not it
    assert s.kernel_s(("streaming_matmul",)) == pytest.approx(3e-6)
    assert s.kernel_s(("streaming",)) == 0
    b = s.breakdown()
    assert b["device_ops"] == [["streaming_matmul.38", pytest.approx(3e-6)],
                               ["fusion.3", pytest.approx(1e-6)]]
    # idle [500,1000) [3500,6000) [7000,10500); the step module covers
    # [3500,4000) of it, the marker module none
    assert dict(b["idle_gaps"]) == {"inside a step program": pytest.approx(0.5e-6),
                                    "between step programs": pytest.approx(6.0e-6)}


def test_a_trace_without_the_marker_reduces_to_nothing():
    from jax._src.profiler import ProfileData

    unmarked = HAND_MADE.replace("jit_chipbench_window_mark", "jit_other")
    assert trace_reduce.reduce(ProfileData.from_text_proto(unmarked), 10e-6) is None


def test_recorded_chip_trace_reduces_to_hand_counts():
    """``fixtures/streaming_matmul_x3.xplane.pb`` (``make_fixture.py`` on a
    TPU v5e): the window marker, then three steps of a jitted
    ``tanh(streaming_matmul(a, b)) + 1``. Read off by hand (ns): the marker
    module starts at 42,226,922; the step modules run [44,784,991 +8,797],
    [48,030,484 +8,912], [51,350,854 +8,763]; streaming_matmul.1 at
    44,785,000 (8,160), 48,030,491 (8,273), 51,350,861 (8,123), and
    tanh_add_fusion at 44,793,160 (627), 48,038,766 (628), 51,358,986 (630)."""
    from jax._src.profiler import ProfileData

    data = ProfileData.from_file(str(FIXTURES / "streaming_matmul_x3.xplane.pb"))
    s = trace_reduce.reduce(data, 0.01)
    assert s.n_ops == 6
    busy = 8160 + 627 + 8273 + 628 + 8123 + 630
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.kernel_s(("streaming_matmul",)) == pytest.approx((8160 + 8273 + 8123) * 1e-9)
    # idle inside the step modules: 9 + 0 + 1, 7 + 2 + 2 and 7 + 2 + 1 ns
    # (before the kernel, between kernel and fusion, after the fusion)
    gaps = dict(s.breakdown()["idle_gaps"])
    assert gaps["inside a step program"] == pytest.approx(31e-9)
    assert gaps["between step programs"] == pytest.approx((10_000_000 - busy - 31) * 1e-9)
