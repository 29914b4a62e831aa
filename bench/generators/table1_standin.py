"""The schema-matched stand-in for a Table-1 training set with only
discrete attributes (U.S. Census): uniform codes with fixed cardinalities,
labelled by a random ground-truth tree of depth 12 with 8% label noise.

A copy of the program's ``repro.data.datasets`` stand-in, equal to it bit
for bit at the configuration's size (``bench/tests/test_data.py``).
"""

from __future__ import annotations

import zlib

import numpy as np

from bench import data


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
    return data.binned(cols, [False] * len(cols), y, n_classes, 0,
                       n_bins_disc=cardinalities)


def make(spec: dict) -> dict:
    return table1_standin(spec["n_cases"], name=spec["standin_name"],
                          seed=spec["data_seed"],
                          n_classes=spec["n_classes"],
                          cardinalities=spec["cardinalities"])
