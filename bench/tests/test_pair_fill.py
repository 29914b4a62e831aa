"""The ``pair_fill`` reader, on a hand-built registry: it reads its own
gauges, and a program without them nulls no other counter metric."""

import pytest

from bench.metrics import (compact_fill, live_case_share, pair_fill,
                           slot_fill, supersteps_per_tree)
from repro.obs import metrics

OLD_GAUGES = {"frontier_supersteps": 280, "frontier_open_nodes": 63628,
              "frontier_live_case_steps": 14_000_000,
              "frontier_hist_case_steps": 20_000_000,
              "frontier_cases": 500_000, "frontier_slots": 256}


@pytest.fixture
def registry(monkeypatch):
    reg = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    return reg


def _ctx():
    return {"trace": None, "units": 2, "notes": []}


def _set(registry, gauges):
    for name, v in gauges.items():
        registry.gauge(name).set(v)


def test_pair_fill_reads_its_own_gauges(registry):
    _set(registry, {"frontier_supersteps": 280, "frontier_slots": 256,
                    "frontier_attrs": 9, "frontier_tested_pairs": 175_000})
    ctx = _ctx()
    assert pair_fill.read(ctx) == pytest.approx(
        100 * 175_000 / (280 * 256 * 9))
    assert ctx["notes"] == []


def test_pair_fill_without_its_gauges_gives_none_and_a_note(registry):
    ctx = _ctx()
    assert pair_fill.read(ctx) is None
    assert any("pair_fill: the program's registry has no "
               "frontier_tested_pairs" in n for n in ctx["notes"])
    # a program with the pair count but not A
    _set(registry, {"frontier_tested_pairs": 5, "frontier_supersteps": 5,
                    "frontier_slots": 5})
    ctx = _ctx()
    assert pair_fill.read(ctx) is None
    assert any("no frontier_attrs" in n for n in ctx["notes"])


def test_old_counters_read_without_the_pair_gauges(registry):
    # the program before the pair count publishes only the old gauges:
    # every old counter metric still reads, and only pair_fill is missing
    _set(registry, OLD_GAUGES)
    ctx = _ctx()
    assert supersteps_per_tree.read(ctx) == 280
    assert live_case_share.read(ctx) == pytest.approx(
        100 * 14e6 / (280 * 5e5))
    assert compact_fill.read(ctx) == pytest.approx(70.0)
    assert slot_fill.read(ctx) == pytest.approx(100 * 63628 / (280 * 256))
    assert pair_fill.read(ctx) is None
    assert not any("no superstep counters" in n for n in ctx["notes"])
