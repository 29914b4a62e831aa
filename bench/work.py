"""Work the algorithm needs, behind the kernels' roofline shares.

Each count is what C4.5 itself asks for, worked out from the grown tree,
and not what a kernel happens to compute: a later kernel that does less
redundant work is judged against the same numbers.

* Histogram (splitAtt's counting): every node C4.5 tests reads each of its
  cases once, an (A + 2)-word row (A attribute bins, the class and the
  weight, 4 bytes each), and adds the case's weight into one histogram cell
  per attribute: ``cases * A`` additions.
* Split gain: every tested node reads its (A, B + 1, C) float32 histogram
  once.  For each (attribute, bin, class) cell the scan does one add for
  the prefix sum and one subtract for the right side, and each side's
  entropy term costs a multiply, a log and an add: ``GAIN_OPS_PER_CELL``.

A node is tested when it was open and C4.5 did not stop at it before
scoring: it holds cases of two or more classes, weight at least
``2 * min_objs`` and depth below ``max_depth``.
"""

from __future__ import annotations

import numpy as np

WORD = 4
GAIN_OPS_PER_CELL = 8       # prefix add, right subtract, 2 x (mul, log, add)


def tested_nodes(freq: np.ndarray, depth: np.ndarray, *, min_objs: float,
                 max_depth: int) -> np.ndarray:
    """Mask of the live nodes C4.5 scores (see module doc)."""
    w = freq.sum(axis=1)
    mixed = (freq > 1e-7).sum(axis=1) > 1
    return mixed & (w >= 2 * min_objs) & (depth < max_depth)


def histogram(freq, depth, *, n_attrs: int, min_objs: float,
              max_depth: int) -> tuple[float, float]:
    """(operations, bytes) of the histogram pass over one tree's build."""
    t = tested_nodes(freq, depth, min_objs=min_objs, max_depth=max_depth)
    cases = float(freq[t].sum())
    return cases * n_attrs, cases * (n_attrs + 2) * WORD


def split_gain(freq, depth, *, n_attrs: int, n_bins: int, n_classes: int,
               min_objs: float, max_depth: int) -> tuple[float, float]:
    """(operations, bytes) of split scoring over one tree's build; ``n_bins``
    is the histogram's padded bin count B (one more cell for unknowns)."""
    t = tested_nodes(freq, depth, min_objs=min_objs, max_depth=max_depth)
    cells = float(t.sum()) * n_attrs * (n_bins + 1) * n_classes
    return cells * GAIN_OPS_PER_CELL, cells * WORD


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float | None, str]:
    """Least time the chip needs for the work over the time it took, in %,
    and which bound sets the least time (``compute`` or ``memory``)."""
    if seconds <= 0 or (ops <= 0 and nbytes <= 0):
        return None, ""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
