"""QUEST/Agrawal classification function 5 (Agrawal et al., "An Interval
Classifier for Database Mining Applications", VLDB 1992), the generator of
the paper's SyD10M9A: 6 continuous and 3 discrete attributes, 2 classes,
5% of labels flipped.  Continuous columns are binned to rank space with at
most ``n_bins`` quantile bins whose edges are domain values.

A copy of the program's ``repro.data.quest``, equal to it bit for bit
(``bench/tests/test_data.py``).
"""

from __future__ import annotations

import numpy as np

from bench import data

IS_CONT = (True,) * 6 + (False,) * 3


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
    return data.binned(columns, IS_CONT, y, 2, max_bins)


def make(spec: dict) -> dict:
    return quest5(spec["n_cases"], seed=spec["data_seed"],
                  max_bins=spec["n_bins"],
                  perturbation=spec["perturbation"])
