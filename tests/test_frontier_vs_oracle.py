"""Property: the SPMD frontier engine is exactly the sequential oracle."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from conftest import STANDIN_NAMES, make_tree_dataset, standin
from repro.core import c45, frontier
from repro.core.config import GrowConfig
from repro.core.tree import predict, trees_equal


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(30, 500),
    n_cont=st.integers(0, 3),
    n_disc=st.integers(0, 3),
    n_classes=st.integers(2, 4),
    slots=st.sampled_from([2, 7, 64]),
    unknown=st.sampled_from([0.0, 0.15]),
)
def test_engines_identical(seed, n, n_cont, n_disc, n_classes, slots,
                           unknown):
    if n_cont + n_disc == 0:
        n_cont = 1
    rng = np.random.default_rng(seed)
    ds = make_tree_dataset(rng, n, n_cont=n_cont, n_disc=n_disc,
                           n_classes=n_classes, unknown_frac=unknown)
    cfg = GrowConfig(max_nodes=1 << 13, frontier_slots=slots)
    t_seq = c45.build(ds, cfg, capacity=cfg.max_nodes)
    t_ff = frontier.build(ds, cfg)
    assert trees_equal(t_seq, t_ff), (
        f"trees differ: seq={t_seq.size} ff={t_ff.size}")
    p1 = np.asarray(predict(t_seq, ds.x, ds.attr_is_cont))
    p2 = np.asarray(predict(t_ff, ds.x, ds.attr_is_cont))
    assert (p1 == p2).all()


@pytest.mark.parametrize("criterion", ["gain", "gain_ratio"])
@pytest.mark.parametrize("name", STANDIN_NAMES)
def test_jnp_path_matches_oracle(name, criterion):
    ds = standin(name)
    cfg = GrowConfig(max_nodes=4096, frontier_slots=32, criterion=criterion)
    t_seq = c45.build(ds, cfg, capacity=cfg.max_nodes)
    t_ff = frontier.build(ds, cfg, impl="jnp")
    assert trees_equal(t_seq, t_ff), "jnp tree != sequential oracle"
    p1 = np.asarray(predict(t_seq, ds.x, ds.attr_is_cont))
    p2 = np.asarray(predict(t_ff, ds.x, ds.attr_is_cont))
    assert (p1 == p2).all()


def _pruned_prefix(small, full):
    """True when ``small`` is ``full`` with some subtrees cut to leaves:
    the two are walked together from the root, every node of ``small``
    has its ``full`` counterpart's frequencies, class and depth, and every
    internal one its split."""
    small, full = small.to_numpy(), full.to_numpy()
    todo = [(0, 0)]
    while todo:
        i, j = todo.pop()
        if (small.node_class[i] != full.node_class[j]
                or small.node_depth[i] != full.node_depth[j]
                or not np.allclose(small.node_freq[i], full.node_freq[j],
                                   atol=1e-3, rtol=1e-4)):
            return False
        n = int(small.node_nchild[i])
        if n == 0:
            continue
        if (n != int(full.node_nchild[j])
                or small.node_attr[i] != full.node_attr[j]
                or small.node_split_bin[i] != full.node_split_bin[j]):
            return False
        todo += [(int(small.node_child0[i]) + c, int(full.node_child0[j]) + c)
                 for c in range(n)]
    return True


@pytest.mark.parametrize("name", STANDIN_NAMES)
def test_capacity_overflow_degrades_gracefully(name):
    """A node table too small for the tree forces leaves: the result is the
    oracle's tree with subtrees cut off, never an error."""
    ds = standin(name)
    cfg = GrowConfig(max_nodes=16, frontier_slots=8)
    tree = frontier.build(ds, cfg)          # must not error
    full = c45.build(ds, cfg)
    assert full.size > 16 >= tree.size
    assert _pruned_prefix(tree, full)
    pred = np.asarray(predict(tree, ds.x, ds.attr_is_cont))
    assert pred.shape == (ds.n_cases,)


@pytest.mark.parametrize("max_depth", [1, 4])
@pytest.mark.parametrize("name", STANDIN_NAMES)
def test_max_depth_respected(name, max_depth):
    ds = standin(name)
    cfg = GrowConfig(max_depth=max_depth, max_nodes=4096)
    t_seq = c45.build(ds, cfg, capacity=4096)
    t_ff = frontier.build(ds, cfg)
    assert trees_equal(t_seq, t_ff)
    assert t_ff.depth <= max_depth


def test_collect_stats_reports_cost_model(rng):
    ds = make_tree_dataset(rng, 600, n_cont=2, n_disc=1)
    cfg = GrowConfig(frontier_slots=16, cost_model="nsq", max_nodes=8192)
    tree, stats = frontier.build(ds, cfg, collect_stats=True)
    assert len(stats) >= 1
    assert stats[0]["n_processed"] == 1           # root superstep
    # NAP is chosen at the root (coarse grain) under |T| < c r^2
    assert stats[0]["nap_nodes"] == 1
    assert sum(s["n_processed"] for s in stats) == tree.size
