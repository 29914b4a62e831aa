"""The phase and counter readers, on a hand-built trace, map and registry."""

import pytest

from bench import scopes, trace_reduce
from bench.metrics import (compact_fill, compact_ms_per_tree,
                           live_case_share, route_ms_per_tree,
                           select_ms_per_tree, slot_fill,
                           split_att_ms_per_tree, split_post_ms_per_tree,
                           split_pre_ms_per_tree, supersteps_per_tree)
from repro.core import frontier
from repro.obs import metrics

MS = 1_000_000                  # ns

READERS = {"frontier.split_pre": split_pre_ms_per_tree,
           "frontier.select": select_ms_per_tree,
           "frontier.split_att": split_att_ms_per_tree,
           "frontier.compact": compact_ms_per_tree,
           "frontier.split_post": split_post_ms_per_tree,
           "frontier.route": route_ms_per_tree}

# Two builds' device ops: a while loop 0-90 ms holding the phases' ops, an
# op outside any scope (a prefetch) and the set-up's fill before it.
OPS = [("%fill.1 = s32[8]{0} broadcast(...)", 0, 2 * MS),
       ("%while.3 = (s32[4]) while(...)", 2 * MS, 88 * MS),
       ("%fusion.201 = s32[256]{0} fusion(...)", 3 * MS, 6 * MS),
       ("%fusion.241 = s32[500000]{0} fusion(...)", 10 * MS, 10 * MS),
       ("%fusion.9 = s32[8]{0} fusion(...)", 21 * MS, 4 * MS),
       ("%frontier_histogram.59 = f32[9,2] custom-call(...)", 26 * MS,
        20 * MS),
       ("%fusion.54 = s32[4096]{0} fusion(...)", 47 * MS, 8 * MS),
       ("%fusion.245 = s32[500000]{0} fusion(...)", 56 * MS, 12 * MS),
       ("%fusion.77 = f32[256,2]{0} fusion(...)", 69 * MS, 9 * MS),
       ("%copy-start.4 = (s32[10]) copy-start(...)", 79 * MS, 1 * MS)]
# (scope, rule): the fill's is set-up's (rule 4), fusion.9 a neighbour's
# (rule 3); the rest are read from metadata (rules 1 and 2).
MAP = {"fill.1": ("frontier.init", 4),
       "fusion.201": ("frontier.select", 2),
       "fusion.241": ("frontier.split_pre", 2),
       "fusion.9": ("frontier.split_att", 3),
       "frontier_histogram.59": ("frontier.split_att", 1),
       "fusion.54": ("frontier.compact", 2),
       "fusion.245": ("frontier.route", 2),
       "fusion.77": ("frontier.split_post", 2)}


def _ctx(units=2):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": OPS}]}]
    return {"trace": trace_reduce.reduce_planes(planes), "units": units,
            "notes": []}


@pytest.fixture
def scope_map(monkeypatch):
    monkeypatch.setattr(frontier, "build_scopes", lambda: dict(MAP))


def test_phases_and_unmapped_add_up_to_busy(scope_map):
    ctx = _ctx()
    got = {scope: r.read(ctx) for scope, r in READERS.items()}
    # the while loop's self time (88 - 70 ms of nested ops) and the
    # prefetch belong to no scope; the fill to set-up
    assert got == pytest.approx({
        "frontier.split_pre": 8.0, "frontier.select": 3.0,
        "frontier.split_att": 16.0, "frontier.compact": 4.0,
        "frontier.split_post": 10.5, "frontier.route": 6.0})
    _, unmapped_s, unmapped, inferred = scopes.by_scope(
        ctx["trace"]["device_ops"], MAP)
    assert [k for k, _ in unmapped] == ["while.3 = ", "copy-start.4 = "]
    assert inferred == pytest.approx({"frontier.init": 2e-3,   # fill.1
                                      "frontier.split_att": 4e-3})
    init_ms = 1.0
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / ctx["units"]
    phases_ms = sum(got[p] for p in ("frontier.split_pre",
                                     "frontier.split_att",
                                     "frontier.split_post"))
    assert phases_ms + init_ms + 1e3 * unmapped_s / 2 == \
        pytest.approx(busy_ms)
    assert "in no frontier.* scope" in ctx["notes"][0]
    total_ms = sum(1e3 * s for _, s in ctx["trace"]["device_ops"])
    assert (f"{100 * 6 / total_ms:.4f}% in a scope inferred by rule 3 or 4 "
            "(3.000 ms per tree: frontier.split_att 2.000 ms, "
            "frontier.init 1.000 ms)") in ctx["notes"][0]
    assert len(ctx["notes"]) == 1               # the map is made once


def test_nested_scope_never_exceeds_its_phase(scope_map):
    ctx = _ctx()
    for nested, phase in scopes.PARENT.items():
        assert READERS[nested].read(ctx) <= READERS[phase].read(ctx)


def test_missing_map_gives_none_and_a_note(monkeypatch):
    monkeypatch.delattr(frontier, "build_scopes")
    ctx = _ctx()
    assert all(r.read(ctx) is None for r in READERS.values())
    assert any("no frontier.build_scopes" in n for n in ctx["notes"])


def test_empty_map_gives_none_and_a_note(monkeypatch):
    monkeypatch.setattr(frontier, "build_scopes", dict)
    ctx = _ctx()
    assert split_att_ms_per_tree.read(ctx) is None
    assert any("gave no map" in n for n in ctx["notes"])


@pytest.fixture
def registry(monkeypatch):
    reg = metrics.Registry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    return reg


def test_counters_from_the_registry(registry):
    for name, v in {"frontier_supersteps": 280, "frontier_open_nodes": 63628,
                    "frontier_live_case_steps": 14_000_000,
                    "frontier_hist_case_steps": 20_000_000,
                    "frontier_cases": 500_000,
                    "frontier_slots": 256}.items():
        registry.gauge(name).set(v)
    ctx = _ctx()
    assert supersteps_per_tree.read(ctx) == 280
    assert live_case_share.read(ctx) == pytest.approx(
        100 * 14e6 / (280 * 5e5))
    assert compact_fill.read(ctx) == pytest.approx(70.0)
    assert slot_fill.read(ctx) == pytest.approx(100 * 63628 / (280 * 256))


def test_missing_counters_give_none_and_a_note(registry):
    registry.gauge("frontier_supersteps").set(3)
    ctx = _ctx()
    assert supersteps_per_tree.read(ctx) is None
    assert live_case_share.read(ctx) is None
    assert compact_fill.read(ctx) is None
    assert slot_fill.read(ctx) is None
    assert sum("no superstep counters" in n for n in ctx["notes"]) == 1
