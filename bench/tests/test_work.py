"""Work counts behind the roofline shares, on a hand-built tree."""

import numpy as np
import pytest

from bench import work

# Root (10 cases: 6 of class 0, 4 of class 1) split into a mixed child of
# 7 cases at depth 1 and a pure child of 3; the mixed child splits into two
# children of 4 and 3 cases at depth 2, one of them mixed but at the depth
# limit.  Only the root and node 1 are tested.
FREQ = np.array([[6, 4], [4, 3], [0, 3], [4, 0], [1, 2]], np.float32)
DEPTH = np.array([0, 1, 1, 2, 2])


def test_tested_nodes_follow_c45_stop_rules():
    got = work.tested_nodes(FREQ, DEPTH, min_objs=2.0, max_depth=2)
    assert got.tolist() == [True, True, False, False, False]
    # min_objs = 4 needs 8 cases: node 1 (7 cases) stops too
    got = work.tested_nodes(FREQ, DEPTH, min_objs=4.0, max_depth=2)
    assert got.tolist() == [True, False, False, False, False]


def test_histogram_counts_each_case_of_each_tested_node():
    ops, nbytes = work.histogram(FREQ, DEPTH, n_attrs=9, min_objs=2.0,
                                 max_depth=2)
    # 10 + 7 = 17 cases; 9 adds each; rows of 9 + 2 words of 4 bytes
    assert ops == 17 * 9
    assert nbytes == 17 * 11 * 4


def test_split_gain_reads_each_tested_histogram_once():
    ops, nbytes = work.split_gain(FREQ, DEPTH, n_attrs=9, n_bins=256,
                                  n_classes=2, min_objs=2.0, max_depth=2)
    cells = 2 * 9 * 257 * 2
    assert nbytes == cells * 4
    assert ops == cells * 8


def test_roofline_share_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = work.roofline_share(50.0, 20.0, 4.0, peak)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = work.roofline_share(1000.0, 20.0, 20.0, peak)
    assert bound == "compute" and share == pytest.approx(50.0)
    assert work.roofline_share(1.0, 1.0, 0.0, peak) == (None, "")
