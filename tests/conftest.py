"""Shared test fixtures.

NOTE: no XLA_FLAGS here on purpose — unit tests must see the real single
CPU device.
"""

import functools
import threading

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def run_with_timeout(fn, seconds=30.0):
    """Run ``fn`` on a daemon thread; fail (don't hang) if it deadlocks.

    Backstop for the fault-path tests: they must *fail* on a regression of
    the farm/engine termination guarantees even when pytest-timeout is not
    installed.  Exceptions from ``fn`` are re-raised in the caller.
    """
    box = {}

    def target():
        try:
            box["val"] = fn()
        except BaseException as e:   # pragma: no cover - surfaced below
            box["exc"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        pytest.fail(f"deadlock: call did not finish within {seconds}s")
    if "exc" in box:
        raise box["exc"]
    return box["val"]


def make_tree_dataset(rng, n=300, *, n_cont=2, n_disc=2, n_classes=2,
                      unknown_frac=0.0, max_bins=64, domain=16):
    """Random small rank-space dataset with learnable structure."""
    from repro.core import binning
    cols, kinds = [], []
    for _ in range(n_cont):
        base = rng.uniform(-2, 2, size=domain)     # small domain => exact bins
        c = rng.choice(base, size=n)
        if unknown_frac:
            c = c.copy()
            c[rng.random(n) < unknown_frac] = np.nan
        cols.append(c)
        kinds.append(True)
    for _ in range(n_disc):
        cols.append(rng.integers(0, int(rng.integers(2, 5)), n))
        kinds.append(False)
    y = rng.integers(0, n_classes, n)
    gate = np.nan_to_num(cols[0], nan=0.0) > 0
    y = np.where(gate, (y + 1) % n_classes, y)     # correlate with col 0
    return binning.fit(cols, y, attr_is_cont=kinds, n_classes=n_classes,
                       max_bins=max_bins)


# The Table-1 stand-ins at CPU scale, for the oracle grids: wide and
# discrete-heavy (census_pums, 40 attrs), QUEST-generated (syd10m9a, 9 attrs,
# continuous-heavy), all-discrete with 5 classes (us_census, 67 attrs of
# 2-11 values), many-class (kddcup99, 23 classes) and deep (forest_cover).
STANDINS = [("census_pums", 0.001), ("syd10m9a", 0.00002),
            ("us_census", 0.001), ("kddcup99", 0.0001),
            ("forest_cover", 0.001)]
STANDIN_NAMES = [name for name, _ in STANDINS]


@functools.lru_cache(maxsize=None)
def standin(name):
    """A stand-in of :data:`STANDINS` at its scale, in 16 bins."""
    from repro.data import datasets
    return datasets.load(name, scale=dict(STANDINS)[name], max_bins=16)
