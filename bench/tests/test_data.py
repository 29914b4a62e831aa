"""The benchmark's copies of the generators make the program's datasets."""

import numpy as np

from bench import data
from bench.generators.quest5 import quest5


def test_quest5_equals_the_program_generator():
    from repro.data import quest
    got = quest5(20_000, seed=3, max_bins=256)
    want = quest.syd(20_000, seed=3)
    assert np.array_equal(got["x"], want.x)
    assert np.array_equal(got["y"], want.y)
    assert np.array_equal(got["n_bins"], want.n_bins)
    assert np.array_equal(got["attr_is_cont"], want.attr_is_cont)


def test_census_standin_equals_the_program_generator():
    from repro.data import datasets
    # a tenth of U.S. Census, the size a later cell is to grow
    want = datasets.load("us_census", scale=0.1, seed=0)
    spec = dict(generator="table1_standin", n_cases=want.n_cases,
                standin_name="us_census", data_seed=0, n_classes=5,
                cardinalities=want.n_bins.tolist())
    got = data.make(spec)
    assert np.array_equal(got["x"], want.x)
    assert np.array_equal(got["y"], want.y)
    assert np.array_equal(got["n_bins"], want.n_bins)


def test_permutation_keeps_the_cases():
    ds = quest5(1000, seed=0, max_bins=256)
    p = data.permuted(ds, 2**31 + 11)
    assert not np.array_equal(p["x"], ds["x"])
    rows = lambda d: sorted(map(tuple, np.c_[d["x"], d["y"]]))  # noqa: E731
    assert rows(p) == rows(ds)
