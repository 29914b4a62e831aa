"""The class axis's counter: ``frontier_present_classes`` counts, on the
host from the returned tree, the (slot, class) histogram columns that held
a case, and the benchmark's ``class_fill`` reader turns it into a share of
the K x C columns every superstep computes."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench.metrics import class_fill, pair_fill, slot_fill
from conftest import make_tree_dataset
from repro.core import frontier
from repro.core.config import GrowConfig
from repro.core.tree import Tree
from repro.obs import metrics
from repro.obs.metrics import Registry


def test_present_classes_counts_the_hand_built_tree():
    freq = np.zeros((8, 4), np.float32)
    freq[0] = [5, 3, 0, 2]          # the root: three classes
    freq[1] = [5, 0, 0, 0]          # a pure child
    freq[2] = [0, 3, 0, 2]
    freq[3] = [0, 0, 0, 1e-8]       # a weight at or under EPS_W holds none
    freq[4] = [9, 9, 9, 9]          # past n_nodes: never grown
    tree = dataclasses.replace(Tree.empty(8, 4), node_freq=jnp.asarray(freq),
                               n_nodes=jnp.int32(4))
    assert float(frontier._PresentClasses(tree)) == 3 + 1 + 2 + 0


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_build_publishes_the_class_gauges(impl):
    ds = make_tree_dataset(np.random.default_rng(3), n=600, n_classes=5)
    cfg = GrowConfig(max_nodes=1024, frontier_slots=8,
                     compact_min_bucket=128)
    reg = Registry()
    tree = frontier.build(ds, cfg, impl=impl, metrics=reg).to_numpy()
    n = int(tree.n_nodes)
    present = reg.gauge("frontier_present_classes").value()
    assert reg.gauge("frontier_classes").value() == ds.n_classes == 5
    assert present == np.sum(tree.node_freq[:n] > frontier.EPS_W)
    # every grown node holds a class; none holds more than C
    assert n <= present <= n * ds.n_classes
    grid = (reg.gauge("frontier_supersteps").value()
            * cfg.frontier_slots * ds.n_classes)
    assert present < grid


@pytest.fixture
def registry(monkeypatch):
    reg = Registry()
    monkeypatch.setattr(metrics, "REGISTRY", reg)
    return reg


def _ctx():
    return {"trace": None, "units": 2, "notes": []}


def _set(registry, gauges):
    for name, v in gauges.items():
        registry.gauge(name).set(v)


def test_class_fill_reads_its_own_gauges(registry):
    _set(registry, {"frontier_supersteps": 40, "frontier_slots": 256,
                    "frontier_classes": 23,
                    "frontier_present_classes": 4_600})
    ctx = _ctx()
    assert class_fill.read(ctx) == pytest.approx(
        100 * 4_600 / (40 * 256 * 23))
    assert ctx["notes"] == []


def test_class_fill_without_its_gauges_gives_none_and_a_note(registry):
    # the program before the class count publishes the older gauges only:
    # class_fill is missing and every older counter metric still reads
    _set(registry, {"frontier_supersteps": 280, "frontier_open_nodes": 63628,
                    "frontier_live_case_steps": 14_000_000,
                    "frontier_hist_case_steps": 20_000_000,
                    "frontier_cases": 500_000, "frontier_slots": 256,
                    "frontier_attrs": 9, "frontier_tested_pairs": 175_000})
    ctx = _ctx()
    assert class_fill.read(ctx) is None
    assert any("class_fill: the program's registry has no "
               "frontier_present_classes" in n for n in ctx["notes"])
    assert slot_fill.read(ctx) == pytest.approx(100 * 63628 / (280 * 256))
    assert pair_fill.read(ctx) == pytest.approx(
        100 * 175_000 / (280 * 256 * 9))
    # a program with the column count but not C
    registry.gauge("frontier_present_classes").set(5)
    ctx = _ctx()
    assert class_fill.read(ctx) is None
    assert any("no frontier_classes" in n for n in ctx["notes"])
