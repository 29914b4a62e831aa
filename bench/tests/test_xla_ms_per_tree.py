"""``xla_ms_per_tree`` leaves the time of every Pallas kernel out of busy
time, the ``live_case_ids`` compaction among them."""

import pytest

from bench import trace_reduce
from bench.metrics import xla_ms_per_tree

MS = 1_000_000                  # ns

# Two builds' device ops: the three kernels and 10 ms of XLA's own ops.
OPS = [("%fusion.1 = s32[8]{0} fusion(...)", 0, 4 * MS),
       ("%frontier_histogram.59 = f32[9,2,264,256]{3,2,1,0} custom-call(...)",
        4 * MS, 20 * MS),
       ("%live_case_ids.3 = s32[2048,128]{1,0} custom-call(...)", 24 * MS,
        6 * MS),
       ("%split_gain.7 = f32[256,9]{1,0} custom-call(...)", 30 * MS, 8 * MS),
       ("%copy.138 = f32[256,9,257,2]{3,2,1,0} copy(...)", 38 * MS, 6 * MS),
       ("%live_case_ids.4 = s32[1024,128]{1,0} custom-call(...)", 50 * MS,
        2 * MS)]


def _ctx(units=2):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": OPS}]}]
    return {"trace": trace_reduce.reduce_planes(planes), "units": units}


def test_kernels_are_left_out_of_busy_time():
    ctx = _ctx()
    assert ctx["trace"]["kernel_s"]["live_case_ids"] == pytest.approx(8e-3)
    assert xla_ms_per_tree.read(ctx) == pytest.approx(10.0 / 2)


@pytest.mark.parametrize("units", [0, None])
def test_no_builds_give_none(units):
    assert xla_ms_per_tree.read(_ctx(units)) is None
