"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

SHAPES = [
    # (N, A, B, C, K)
    (64, 1, 4, 2, 3),
    (200, 3, 13, 4, 10),
    (500, 5, 32, 2, 16),
    (130, 2, 7, 23, 5),     # many classes (KDD-style)
    (96, 4, 128, 3, 8),     # wide bins
]


@pytest.mark.parametrize("n,a,b,c,k", SHAPES)
def test_histogram_matches_ref(n, a, b, c, k):
    rng = np.random.default_rng(n + a)
    x = rng.integers(-1, b, (n, a)).astype(np.int32)
    y = rng.integers(0, c, n).astype(np.int32)
    w = rng.uniform(0.1, 2.0, n).astype(np.float32)
    slot = rng.integers(-1, k, n).astype(np.int32)
    got = np.asarray(ops.frontier_histogram(
        x, y, w, slot, n_slots=k, n_bins=b, n_classes=c))
    want = np.asarray(ref.frontier_histogram_ref(
        x, y, w, slot, n_slots=k, n_bins=b, n_classes=c))
    assert got.shape == (k, a, b + 1, c)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("block_t,block_k,k", [
    (128, 128, 300),    # several case tiles and slot blocks
    (256, 256, 6),      # tiles covering whole axes
    (128, 256, 260)])   # slot axis padded up to the block
def test_histogram_block_shapes(block_t, block_k, k):
    rng = np.random.default_rng(3)
    n, a, b, c = 150, 2, 9, 3
    x = rng.integers(-1, b, (n, a)).astype(np.int32)
    y = rng.integers(0, c, n).astype(np.int32)
    w = np.ones(n, np.float32)
    slot = rng.integers(-1, k, n).astype(np.int32)
    got = np.asarray(ops.frontier_histogram(
        x, y, w, slot, n_slots=k, n_bins=b, n_classes=c,
        block_t=block_t, block_k=block_k))
    want = np.asarray(ref.frontier_histogram_ref(
        x, y, w, slot, n_slots=k, n_bins=b, n_classes=c))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_histogram_rejects_unaligned_lane_tiles():
    """Tiles Mosaic would refuse are refused in interpret mode too."""
    x = np.zeros((300, 2), np.int32)
    z = np.zeros(300, np.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.frontier_histogram(x, z, z.astype(np.float32), z, n_slots=4,
                               n_bins=3, n_classes=2, block_t=100)


def test_histogram_conservation():
    """Total kernel mass == total weight of in-frontier known-valued cells."""
    rng = np.random.default_rng(7)
    n, a, b, c, k = 300, 3, 11, 4, 9
    x = rng.integers(-1, b, (n, a)).astype(np.int32)
    y = rng.integers(0, c, n).astype(np.int32)
    w = rng.uniform(0, 1, n).astype(np.float32)
    slot = rng.integers(-1, k, n).astype(np.int32)
    hist = np.asarray(ops.frontier_histogram(
        x, y, w, slot, n_slots=k, n_bins=b, n_classes=c))
    mask = slot >= 0
    assert hist.sum() == pytest.approx(w[mask].sum() * a, rel=1e-5)


@pytest.mark.parametrize("criterion", ["gain", "gain_ratio"])
@pytest.mark.parametrize("k,a,b,c", [(4, 3, 8, 2), (10, 5, 13, 4),
                                     (3, 2, 64, 3)])
def test_split_gain_matches_ref(k, a, b, c, criterion):
    rng = np.random.default_rng(k * a)
    hist = rng.uniform(0, 10, (k, a, b, c)).astype(np.float32)
    tw = hist.sum((1, 2, 3)) / a + rng.uniform(0, 2, k).astype(np.float32)
    cont = rng.random(a) < 0.6
    nb = rng.integers(2, b + 1, a).astype(np.int32)
    got_s, got_b = ops.split_gain(hist, tw.astype(np.float32), cont, nb,
                                  criterion=criterion)
    want_s, want_b = ref.split_gain_ref(hist, tw.astype(np.float32), cont,
                                        nb, criterion=criterion)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(1, 12),
       a=st.integers(1, 6), b=st.integers(2, 20), c=st.integers(2, 6))
def test_split_gain_property_sweep(seed, k, a, b, c):
    rng = np.random.default_rng(seed)
    hist = (rng.uniform(0, 5, (k, a, b, c)) *
            (rng.random((k, a, b, c)) < 0.7)).astype(np.float32)
    tw = hist.sum((1, 2, 3)).astype(np.float32) / max(a, 1)
    cont = rng.random(a) < 0.5
    nb = rng.integers(2, b + 1, a).astype(np.int32)
    got_s, got_b = ops.split_gain(hist, tw, cont, nb)
    want_s, want_b = ref.split_gain_ref(hist, tw, cont, nb)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_b), np.asarray(want_b))


def test_pallas_engine_end_to_end(rng):
    """frontier.build(impl='pallas') == sequential oracle."""
    from conftest import make_tree_dataset
    from repro.core import c45, frontier
    from repro.core.config import GrowConfig
    from repro.core.tree import trees_equal
    ds = make_tree_dataset(rng, 250, n_cont=2, n_disc=1, max_bins=32)
    cfg = GrowConfig(max_nodes=2048, frontier_slots=8)
    t_seq = c45.build(ds, cfg, capacity=2048)
    t_pal = frontier.build(ds, cfg, impl="pallas")
    assert trees_equal(t_seq, t_pal)
