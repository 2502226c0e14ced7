"""The window's arithmetic: all the work over all the time, a tail over
every step."""

from __future__ import annotations

import pytest

from chipbench.harness import end_to_end, step_times


def test_steps_run_from_one_batch_request_to_the_next():
    # batches asked for at 0, 2, 5; the last step's own wall is 1.5
    assert step_times([10.0, 12.0, 15.0], 1.5) == [2.0, 3.0, 1.5]


def test_rate_is_all_the_work_over_the_whole_window():
    v = end_to_end("tokens", 512, [2.0, 3.0, 1.5], setup_s=40.0)
    assert v["tokens_per_s"] == pytest.approx(3 * 512 / 6.5)
    assert v["setup_s"] == 40.0


def test_tail_is_taken_over_every_step():
    steps = [0.010] * 95 + [0.020] * 5
    v = end_to_end("images", 32, steps, setup_s=1.0)
    # numpy's linear 95th percentile of 95 tens and 5 twenties
    assert v["step_ms_p95"] == pytest.approx(10.5)


def test_a_stall_in_the_window_lowers_the_rate_and_raises_the_tail():
    steady = [0.010] * 100
    stalled = [0.010] * 90 + [0.050] * 10
    a, b = (end_to_end("images", 32, s, setup_s=1.0) for s in (steady, stalled))
    assert b["images_per_s"] < a["images_per_s"]
    assert b["step_ms_p95"] > a["step_ms_p95"]
    # a stall within one step, seen through the batch request times
    times = step_times([0.0, 0.01, 0.02, 0.50, 0.51], 0.01)
    assert max(times) == pytest.approx(0.48)
    assert end_to_end("images", 32, times, 1.0)["images_per_s"] == pytest.approx(5 * 32 / 0.52)
