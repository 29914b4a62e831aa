"""The benchmark's own copies of the two training-set generators.

The program's generators (``repro.data.quest`` and ``repro.data.datasets``)
may change in later work; the yardstick may not.  So the inputs every cell
grows from are made here, by copies kept with the benchmark:

* ``quest5``: QUEST/Agrawal classification function 5 (Agrawal et al., "An
  Interval Classifier for Database Mining Applications", VLDB 1992), the
  generator of the paper's SyD10M9A: 6 continuous and 3 discrete attributes,
  2 classes, 5% of labels flipped.  Continuous columns are binned to rank
  space with at most ``n_bins`` quantile bins whose edges are domain values.
* ``table1_standin``: the schema-matched stand-in for a Table-1 training set
  with only discrete attributes (U.S. Census): uniform codes with fixed
  cardinalities, labelled by a random ground-truth tree of depth 12 with 8%
  label noise.

Both reproduce the program's generators bit for bit at the configurations'
sizes (``bench/tests/test_data.py``).  A dataset is a plain dict of numpy
arrays; :func:`make` builds it from a configuration file's ``data`` block.
"""

from __future__ import annotations

import zlib

import numpy as np

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


def _binned(columns, is_cont, y, n_classes, max_bins, n_bins_disc=None):
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


# ---------------------------------------------------------- QUEST fn. 5

QUEST_IS_CONT = (True,) * 6 + (False,) * 3


def quest5(n: int, *, seed: int, max_bins: int,
           perturbation: float = 0.05) -> dict:
    rng = np.random.default_rng(seed)
    salary = rng.uniform(20_000, 150_000, n)
    commission = np.where(salary >= 75_000, 0.0,
                          rng.uniform(10_000, 75_000, n))
    age = rng.uniform(20, 80, n)
    elevel = rng.integers(0, 5, n)
    car = rng.integers(0, 20, n)
    zipcode = rng.integers(0, 9, n)
    hvalue = rng.uniform(50_000, 150_000, n) * (zipcode + 1) * 0.5
    hyears = rng.uniform(1, 30, n)
    loan = rng.uniform(0, 500_000, n)
    group_a = np.select(
        [age < 40, age < 60],
        [(50_000 <= salary) & (salary <= 100_000)
         & (100_000 <= loan) & (loan <= 300_000),
         (75_000 <= salary) & (salary <= 125_000)
         & (200_000 <= loan) & (loan <= 400_000)],
        (25_000 <= salary) & (salary <= 75_000)
        & (300_000 <= loan) & (loan <= 500_000))
    y = np.where(group_a, 0, 1).astype(np.int32)
    if perturbation > 0:
        flip = rng.random(n) < perturbation
        y = np.where(flip, 1 - y, y)
    columns = (salary, commission, age, hvalue, hyears, loan, elevel, car,
               zipcode)
    return _binned(columns, QUEST_IS_CONT, y, 2, max_bins)


# ------------------------------------------------ Table-1 stand-in labels

def _random_tree_labels(cols, is_cont, n_classes, rng, depth=12,
                        noise=0.08) -> np.ndarray:
    n = len(cols[0])
    y = np.zeros(n, np.int32)

    def grow(idx, d):
        if d == 0 or len(idx) < 64:
            y[idx] = rng.integers(0, n_classes)
            return
        a = int(rng.integers(0, len(cols)))
        col = cols[a][idx]
        if is_cont[a]:
            left = col <= np.quantile(col, rng.uniform(0.25, 0.75))
        else:
            vals = np.unique(col)
            pick = rng.choice(vals, size=max(1, len(vals) // 2),
                              replace=False)
            left = np.isin(col, pick)
        if left.all() or not left.any():
            y[idx] = rng.integers(0, n_classes)
            return
        grow(idx[left], d - 1)
        grow(idx[~left], d - 1)

    grow(np.arange(n), depth)
    flip = rng.random(n) < noise
    y[flip] = rng.integers(0, n_classes, int(flip.sum()))
    return y


def standin_columns(n: int, *, name: str, seed: int, n_attrs: int):
    """The stand-in's columns, in the program generator's draw order, with
    the cardinalities drawn on the way (they depend on ``n``)."""
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % (1 << 16))
    cols, drawn = [], []
    for _ in range(n_attrs):
        h = int(rng.integers(2, 12))
        drawn.append(h)
        cols.append(rng.integers(0, h, n))
    return cols, drawn, rng


def table1_standin(n: int, *, name: str, seed: int, n_classes: int,
                   cardinalities: list[int]) -> dict:
    """All-discrete stand-in, checked against the configuration's
    cardinalities."""
    cols, drawn, rng = standin_columns(n, name=name, seed=seed,
                                       n_attrs=len(cardinalities))
    if drawn != list(cardinalities):
        raise ValueError(f"{name}: drawn cardinalities {drawn} differ from "
                         f"the configuration's {list(cardinalities)}")
    y = _random_tree_labels(cols, [False] * len(cols), n_classes, rng)
    return _binned(cols, [False] * len(cols), y, n_classes, 0,
                   n_bins_disc=cardinalities)


def make(spec: dict) -> dict:
    """The dataset of a configuration's ``data`` block."""
    kind = spec["generator"]
    if kind == "quest5":
        return quest5(spec["n_cases"], seed=spec["data_seed"],
                      max_bins=spec["n_bins"],
                      perturbation=spec["perturbation"])
    if kind == "table1_standin":
        return table1_standin(spec["n_cases"], name=spec["standin_name"],
                              seed=spec["data_seed"],
                              n_classes=spec["n_classes"],
                              cardinalities=spec["cardinalities"])
    raise ValueError(f"unknown generator {kind!r}")


def permuted(ds: dict, seed: int) -> dict:
    """The same cases in the order drawn from ``seed``.  A C4.5 tree depends
    only on the class counts of each candidate partition, so every seed asks
    for the same tree and the same work."""
    p = np.random.default_rng(seed).permutation(len(ds["y"]))
    return dict(ds, x=ds["x"][p], y=ds["y"][p])
