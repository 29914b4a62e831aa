"""What every training-set generator of the benchmark shares.

The program's generators (``repro.data``) may change in later work; the
yardstick may not.  So the inputs every cell grows from are made by copies
kept with the benchmark, one file each, found by the name in a
configuration's ``data`` block:

  bench/generators/<generator>.py   ``make(spec) -> dict``

A dataset is a plain dict of numpy arrays: ``x`` (cases × attributes, bin
codes), ``y``, ``n_bins``, ``attr_is_cont`` and ``n_classes``.  A new
schema is a new generator file; this module names none.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from bench import run as harness

GENERATORS = Path(__file__).resolve().parent / "generators"


def make(spec: dict) -> dict:
    """The dataset of a configuration's ``data`` block, from the generator
    file its ``generator`` names."""
    name = spec["generator"]
    gen = harness.load_module(GENERATORS / f"{name}.py",
                              f"bench_generator_{name}")
    return gen.make(spec)


# --------------------------------------------------------------- binning

def bin_continuous(col: np.ndarray, max_bins: int) -> np.ndarray:
    """Rank-space bins of a float column: exact ranks when the domain has at
    most ``max_bins`` values, else nearest-quantile cuts below the maximum."""
    vals = col.astype(np.float64)
    domain = np.unique(vals)
    if domain.size <= max_bins:
        return np.searchsorted(domain, vals).astype(np.int32)
    qs = np.linspace(0.0, 1.0, max_bins + 1)[1:-1]
    cut = np.unique(np.quantile(domain, qs, method="nearest"))
    cut = cut[cut < domain[-1]]
    return np.searchsorted(cut, vals, side="left").astype(np.int32)


def binned(columns, is_cont, y, n_classes, max_bins, n_bins_disc=None):
    """A dataset from raw columns: continuous ones binned by
    :func:`bin_continuous`, discrete ones kept as codes with ``n_bins_disc``
    values each (by default, their largest code plus one)."""
    cols, n_bins = [], []
    for j, (col, cont) in enumerate(zip(columns, is_cont)):
        if cont:
            b = bin_continuous(col, max_bins)
            n_bins.append(int(b.max()) + 1)
        else:
            b = np.asarray(col, np.int64).astype(np.int32)
            n_bins.append(int(b.max()) + 1 if n_bins_disc is None
                          else int(n_bins_disc[j]))
        cols.append(b)
    return dict(x=np.stack(cols, axis=1), y=np.asarray(y, np.int32),
                attr_is_cont=np.asarray(is_cont, bool),
                n_bins=np.asarray(n_bins, np.int32), n_classes=int(n_classes))


def permuted(ds: dict, seed: int) -> dict:
    """The same cases in the order drawn from ``seed``.  A C4.5 tree depends
    only on the class counts of each candidate partition, so every seed asks
    for the same tree and the same work."""
    p = np.random.default_rng(seed).permutation(len(ds["y"]))
    return dict(ds, x=ds["x"][p], y=ds["y"][p])
