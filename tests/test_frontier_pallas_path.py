"""impl="pallas" must exercise BOTH kernels and still equal the oracle.

Spies on the :mod:`repro.kernels.ops` entry points (the only route from the
frontier engine to the Pallas kernels) prove the histogram *and* the fused
split-gain kernel are actually on the hot path — a regression here silently
reverts splitAtt to the jnp reference and nobody notices until a profile.
"""

import functools

import jax
import numpy as np
import pytest
from conftest import STANDIN_NAMES, standin

from repro.core import c45, frontier
from repro.core.config import GrowConfig
from repro.core.tree import predict, trees_equal
from repro.data import datasets
from repro.kernels import compaction, ops


@pytest.fixture
def kernel_spies(monkeypatch):
    calls = {"histogram": 0, "split_gain": 0}
    real_hist, real_gain = ops.frontier_histogram, ops.split_gain

    def spy_hist(*a, **kw):
        calls["histogram"] += 1
        return real_hist(*a, **kw)

    def spy_gain(*a, **kw):
        calls["split_gain"] += 1
        return real_gain(*a, **kw)

    monkeypatch.setattr(ops, "frontier_histogram", spy_hist)
    monkeypatch.setattr(ops, "split_gain", spy_gain)
    # the build jit is cached per (prob, impl); force a retrace so the spies
    # observe the kernel calls of *this* test
    jax.clear_caches()
    return calls


def _cfg(criterion, **kw):
    return GrowConfig(max_nodes=4096, frontier_slots=32,
                      compact_min_bucket=64, criterion=criterion, **kw)


@functools.lru_cache(maxsize=None)
def _oracle(name, criterion):
    ds = standin(name)
    return c45.build(ds, _cfg(criterion), capacity=4096)


@pytest.mark.parametrize("criterion", ["gain", "gain_ratio"])
@pytest.mark.parametrize("compact", [True, False],
                         ids=["compact", "no_compact"])
@pytest.mark.parametrize("name", STANDIN_NAMES)
def test_pallas_path_uses_both_kernels_and_matches_oracle(
        name, compact, criterion, kernel_spies):
    ds = standin(name)
    t_seq = _oracle(name, criterion)
    t_pal = frontier.build(ds, _cfg(criterion, compact=compact),
                           impl="pallas")

    assert kernel_spies["histogram"] >= 1, "histogram kernel not on hot path"
    assert kernel_spies["split_gain"] >= 1, "split_gain kernel not on hot path"
    if compact:
        # with N > min_bucket the compaction ladder has several buckets, and
        # the switch traces the histogram kernel once per bucket
        n_buckets = len(compaction.bucket_sizes(ds.n_cases, min_bucket=64))
        assert n_buckets > 1
        assert kernel_spies["histogram"] >= n_buckets
    else:
        assert kernel_spies["histogram"] == kernel_spies["split_gain"] == 1

    assert trees_equal(t_seq, t_pal), "pallas tree != sequential oracle"
    p_seq = np.asarray(predict(t_seq, ds.x, ds.attr_is_cont))
    p_pal = np.asarray(predict(t_pal, ds.x, ds.attr_is_cont))
    assert (p_seq == p_pal).all()


def test_split_gain_scores_match_jnp_scoring():
    """The kernel's (K, A) planes vs entropy.gains_from_histogram.

    Split bins must agree exactly.  The kernel repeats the oracle's op
    order, so with integer weights its scores are the oracle's bit for bit
    (``chip_smoke.py`` checks that on a TPU).  These weights are
    fractional, and then the scores agree to f32 rounding only: the kernel's
    prefix sums are a triangular matmul where the oracle uses ``cumsum``,
    and interpret mode fuses the two sides differently, so a class weight
    ``x`` can differ in its last bit or two.  A score is a signed sum of at
    most 2C+4 terms ``x*log2(x)`` (parent, left and right, each a total and
    C class parts), each at most ``W*log2(W)``, divided by W; a last-bit
    change in ``x`` moves its term by about ``eps * x * log2(x)``, and each
    sum adds one rounding.  Allowing 4 ulp per term, the scores can differ
    by up to ``(2C+4) * 4 * eps * log2(W)`` in absolute terms."""
    import jax.numpy as jnp
    from repro.core import entropy

    eps = float(np.finfo(np.float32).eps)
    rng = np.random.default_rng(11)
    for k, a, b, c in [(8, 8, 8, 5), (5, 9, 13, 3), (16, 3, 32, 2)]:
        hist = (rng.uniform(0, 8, (k, a, b, c))
                * (rng.random((k, a, b, c)) < .7)).astype(np.float32)
        tw = hist.sum((1, 2, 3)).astype(np.float32) / a
        cont = rng.random(a) < .5
        nb = rng.integers(2, b + 1, a).astype(np.int32)
        w_max = float(hist.sum((2, 3)).max())
        atol = (2 * c + 4) * 4 * eps * np.log2(w_max)
        for crit in ("gain", "gain_ratio"):
            s_ref, b_ref = entropy.gains_from_histogram(
                jnp.asarray(hist), total_w=jnp.asarray(tw),
                attr_is_cont=jnp.asarray(cont), n_bins=jnp.asarray(nb),
                criterion=crit)
            s_ker, b_ker = ops.split_gain(hist, tw, cont, nb, criterion=crit)
            np.testing.assert_array_equal(np.asarray(b_ref),
                                          np.asarray(b_ker))
            np.testing.assert_allclose(np.asarray(s_ker),
                                       np.asarray(s_ref), rtol=0, atol=atol)


def test_census_tree_passes_the_float64_reference():
    # bench/c45_ref.py recomputes every node's C4.5 decision in float64 from
    # the raw cases and imports nothing of the program
    from bench import c45_ref
    ds = datasets.load("us_census", scale=0.001)
    cfg = GrowConfig(max_nodes=4096, frontier_slots=32,
                     compact_min_bucket=64)
    tree = frontier.build(ds, cfg, impl="pallas").to_numpy()
    n = tree.size
    fields = ("node_attr", "node_split_bin", "node_child0", "node_nchild",
              "node_class", "node_freq", "node_depth")
    got = c45_ref.check_tree(
        dict(x=ds.x, y=ds.y, attr_is_cont=ds.attr_is_cont, n_bins=ds.n_bins,
             n_classes=ds.n_classes),
        dict({f: getattr(tree, f)[:n] for f in fields}, n_nodes=n),
        dict(min_objs=cfg.min_objs, max_depth=cfg.max_depth, eps_gain=1e-6))
    assert (tree.node_nchild[:n] > 2).any()          # multiway splits
    assert got["freq_mismatch_nodes"] == 0
    assert got["structure_faults"] == 0, got["fault_kinds"]
    assert got["gain_gap_bits"] < 1e-9
