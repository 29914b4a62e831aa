"""Data substrate: QUEST generator and the Table-1 stand-ins."""

import numpy as np
import pytest

from repro.data import datasets, quest


def test_quest_schema_matches_table1():
    ds = quest.generate(2_000, function=5, seed=0)
    spec = datasets.TABLE1["syd10m9a"]
    assert ds.n_attrs == 9
    assert int(ds.attr_is_cont.sum()) == spec.n_continuous == 6
    assert int((~ds.attr_is_cont).sum()) == spec.n_discrete == 3
    assert ds.n_classes == 2
    # label noise default 5%: both classes present
    assert set(np.unique(ds.y)) == {0, 1}


def test_quest_function5_learnable():
    from repro.core import GrowConfig, predict
    from repro.core import frontier
    ds = quest.generate(4_000, function=5, seed=1, perturbation=0.0)
    tree = frontier.build(ds, GrowConfig(max_nodes=1 << 13,
                                         frontier_slots=64))
    pred = np.asarray(predict(tree, ds.x, ds.attr_is_cont))
    assert (pred == ds.y).mean() > 0.97     # age/salary/loan bands are crisp


@pytest.mark.parametrize("fn", [1, 2, 3, 4, 5])
def test_quest_functions_produce_both_classes(fn):
    ds = quest.generate(2_000, function=fn, seed=0, perturbation=0.0)
    frac = ds.y.mean()
    assert 0.02 < frac < 0.98, f"function {fn} degenerate: {frac}"


def test_quest_deterministic():
    a = quest.generate(500, seed=7)
    b = quest.generate(500, seed=7)
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.y, b.y)


@pytest.mark.parametrize("name", list(datasets.TABLE1))
def test_table1_standins_schema(name):
    spec = datasets.TABLE1[name]
    ds = datasets.load(name, scale=0.002)
    assert ds.n_attrs == spec.n_discrete + spec.n_continuous
    assert int(ds.attr_is_cont.sum()) == spec.n_continuous
    assert ds.n_classes == spec.n_classes
