"""Acceptance: farm-built trees are oracle-equal even under injected chaos.

The seeded :class:`~repro.core.faults.FaultInjector` crashes task attempts
at p=0.2 and kills one worker permanently; the supervised farm must retry /
re-dispatch until the full C4.5 tree is grown, elementwise-equal to the
sequential oracle, without ever deadlocking (``run_with_timeout`` turns a
hang into a failure).
"""

import numpy as np
import pytest

from conftest import (STANDIN_NAMES, make_tree_dataset, run_with_timeout,
                      standin)
from repro.core import c45, faults, frontier
from repro.core.config import GrowConfig
from repro.core.farm import FaultPolicy
from repro.core.scheduler import make_policy
from repro.core.farm_build import QuarantinedNodes, build
from repro.core.tree import predict, trees_equal

pytestmark = pytest.mark.timeout(300)

CFG = GrowConfig(max_nodes=1 << 13)


def _dataset(seed=0, n=400, **kw):
    rng = np.random.default_rng(seed)
    kw.setdefault("n_cont", 2)
    kw.setdefault("n_disc", 2)
    kw.setdefault("n_classes", 3)
    return make_tree_dataset(rng, n, **kw)


def test_farm_build_matches_oracle_without_faults():
    ds = _dataset()
    t_seq = c45.build(ds, CFG)
    t_farm = run_with_timeout(lambda: build(ds, CFG, n_workers=4), 120)
    assert trees_equal(t_seq, t_farm)


@pytest.mark.parametrize("policy", ["drr", "od", "ws", "health_ws"])
@pytest.mark.parametrize("name", STANDIN_NAMES)
def test_farm_build_matches_oracle_under_every_policy(name, policy):
    """Every scheduling policy grows the oracle's tree; health_ws, told
    worker 0 is dead, also never gives it a task."""
    ds = standin(name)
    t_seq = c45.build(ds, CFG)
    stats = {}
    pol = make_policy(policy, speed_fn=lambda: {0: 0.0})
    t_farm = run_with_timeout(
        lambda: build(ds, CFG, n_workers=3, policy=pol, stats_out=stats),
        120)
    assert trees_equal(t_seq, t_farm)
    assert sum(stats["worker_tasks"]) >= t_seq.size - 1 > 0
    if policy == "health_ws":
        assert stats["worker_tasks"][0] == 0


def test_farm_build_handles_unknowns_and_fractional_weights():
    ds = _dataset(seed=3, unknown_frac=0.15)
    for fractional in (False, True):
        cfg = GrowConfig(max_nodes=1 << 13, unknown_fractional=fractional)
        t_seq = c45.build(ds, cfg)
        t_farm = run_with_timeout(lambda: build(ds, cfg, n_workers=3), 120)
        assert trees_equal(t_seq, t_farm)


def test_farm_build_oracle_equal_under_seeded_chaos():
    """crash_p=0.2 + one permanently dead worker -> identical tree."""
    ds = _dataset()
    t_seq = c45.build(ds, CFG)
    inj = faults.FaultInjector(seed=7, spec=faults.FaultSpec(
        crash_p=0.2, slow_p=0.1, slow_s=0.002,
        dead_workers=frozenset({1})), key_fn=lambda t: t.node_id)
    stats = {}
    t_chaos = run_with_timeout(
        lambda: build(ds, CFG, n_workers=4,
                      fault=FaultPolicy(max_retries=8, seed=3,
                                        backoff_base=1e-4),
                      injector=inj, stats_out=stats), 240)
    assert trees_equal(t_seq, t_chaos), "chaos build diverged from oracle"
    p1 = np.asarray(predict(t_seq, ds.x, ds.attr_is_cont))
    p2 = np.asarray(predict(t_chaos, ds.x, ds.attr_is_cont))
    assert (p1 == p2).all()
    assert stats["failures"] > 0 and stats["retries"] > 0
    assert stats["quarantined"] == 0
    assert stats["dead_workers"] == [1]


def test_farm_build_chaos_is_replayable():
    """Same seed -> same fault schedule -> same farm stats."""
    ds = _dataset(seed=5, n=250)

    def run_once():
        inj = faults.FaultInjector(seed=11, spec=faults.FaultSpec(
            crash_p=0.25), key_fn=lambda t: t.node_id)
        stats = {}
        tree = build(ds, CFG, n_workers=3,
                     fault=FaultPolicy(max_retries=8, backoff_base=0.0),
                     injector=inj, stats_out=stats)
        return tree, stats["failures"], stats["retries"]

    t1, f1, r1 = run_with_timeout(run_once, 120)
    t2, f2, r2 = run_with_timeout(run_once, 120)
    assert trees_equal(t1, t2)
    assert (f1, r1) == (f2, r2)


def test_farm_build_quarantine_degrades_node_to_leaf():
    ds = _dataset(seed=9, n=200)
    inj = faults.FaultInjector(seed=0, spec=faults.FaultSpec(crash_p=1.0),
                               key_fn=lambda t: t.node_id)
    fault = FaultPolicy(max_retries=1, backoff_base=0.0)
    with pytest.raises(QuarantinedNodes):
        run_with_timeout(
            lambda: build(ds, CFG, n_workers=2, fault=fault, injector=inj),
            120)
    # non-strict: the poisoned root degrades to a single-leaf tree
    tree = run_with_timeout(
        lambda: build(ds, CFG, n_workers=2, fault=fault,
                      injector=faults.FaultInjector(
                          seed=0, spec=faults.FaultSpec(crash_p=1.0),
                          key_fn=lambda t: t.node_id),
                      strict=False), 120)
    assert tree.size == 1
    pred = np.asarray(predict(tree, ds.x, ds.attr_is_cont))
    assert pred.shape == (200,)


def test_frontier_build_farm_entrypoint():
    ds = _dataset(seed=2, n=150)
    t_seq = c45.build(ds, CFG)
    t_farm = run_with_timeout(
        lambda: frontier.build_farm(ds, CFG, n_workers=2), 120)
    assert trees_equal(t_seq, t_farm)
