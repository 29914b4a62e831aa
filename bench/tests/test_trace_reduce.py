"""The trace reduction, on a hand-built trace and on one recorded on a v5e."""

from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000                  # ns


def _hand_trace():
    """Host thread: window 0-100 ms, build 0-10 (dispatch 2-8 inside it),
    readback 10-45, build 48-52, readback 52-100.  Device: a while loop
    12-40 holding a histogram kernel 15-20 and a fusion 22-30; a kernel
    55-60 and an async copy 58-70."""
    host = [("bench.window", 0, 100 * MS), ("bench.build", 0, 10 * MS),
            ("dispatch", 2 * MS, 6 * MS), ("bench.readback", 10 * MS,
                                           35 * MS),
            ("bench.build", 48 * MS, 4 * MS),
            ("bench.readback", 52 * MS, 48 * MS)]
    ops = [("%while.3 = (s32[4]) while(...)", 12 * MS, 28 * MS),
           ("%frontier_histogram.7 = f32[2] custom-call(...)", 15 * MS,
            5 * MS),
           ("%fusion.12 = s32[8]{0} fusion(...)", 22 * MS, 8 * MS),
           ("%split_gain.2 = f32[2] custom-call(...)", 55 * MS, 5 * MS)]
    async_ops = [("%copy-start.1 = (f32[4]) copy-start(...)", 58 * MS,
                  12 * MS)]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_x", 12 * MS, 60 * MS)]},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": async_ops}]},
    ]


def test_hand_trace_by_hand():
    r = trace_reduce.reduce_planes(_hand_trace())
    # busy: 12-40 and 55-70 -> 28 + 15 = 43 ms of a 100 ms window
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.043)
    assert r["kernel_s"]["frontier_histogram"] == pytest.approx(0.005)
    assert r["kernel_s"]["split_gain"] == pytest.approx(0.005)
    assert r["kernel_s"]["while"] == pytest.approx(0.028)
    # self time: the while loop's 28 ms less its 5 + 8 ms of children
    ops = dict(r["device_ops"])
    assert ops["while.3 = "] == pytest.approx(0.015)
    assert ops["fusion.12 = s32[8]"] == pytest.approx(0.008)
    # idle 0-12 ms (middle 6: the build's dispatch), 40-55 (middle 47.5:
    # after readback ends at 45, before build starts at 48), 70-100 (middle
    # 85: the second readback)
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.build / dispatch": 0.012,
                                  "outside bench spans": 0.015,
                                  "bench.readback": 0.030})


def test_window_clips_busy_time():
    planes = _hand_trace()
    planes[0]["lines"][0]["events"][0] = ("bench.window", 20 * MS, 40 * MS)
    r = trace_reduce.reduce_planes(planes)
    # inside 20-60: busy 20-40 and 55-60
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.025)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(_hand_trace()[:1])


def test_op_name():
    assert trace_reduce.op_name(
        "%frontier_histogram.23 = f32[9,2] custom-call(s32[16] %p)") \
        == "frontier_histogram"
    assert trace_reduce.op_name("%cond.9.clone.6 = (f32[2]) conditional") \
        == "cond.9.clone"


def test_recorded_v5e_trace():
    """A pallas build of 8,192 SyD10M9A cases and three 64-row predicts of
    a 4-tree forest, traced on a TPU v5e.  Read once by hand from the
    trace: the build's module ran 63.203 ms on the device; the histogram
    kernel's events total 6.02 ms, split_gain's 0.24 ms and the three
    forest_predict kernels' 0.70 ms."""
    r = trace_reduce.reduce(DATA / "tpu_build_predict.xplane.pb")
    assert 0.0632 <= r["busy_s"] <= r["window_s"]
    assert r["kernel_s"]["frontier_histogram"] == pytest.approx(6.02e-3,
                                                                rel=1e-3)
    assert r["kernel_s"]["split_gain"] == pytest.approx(2.37e-4, rel=1e-2)
    assert r["kernel_s"]["forest_predict"] == pytest.approx(7.03e-4,
                                                            rel=1e-2)
    assert {k.split(" / ")[0] for k, _ in r["idle_gaps"]} <= {
        "bench.build", "bench.readback", "bench.step", "outside bench spans"}
