"""Packed Forest: oracle equality of every impl + persistence round-trip."""

import functools

import numpy as np
import pytest
from conftest import STANDIN_NAMES, make_tree_dataset, standin

from repro.core import binning, c45
from repro.core.config import GrowConfig
from repro.core.tree import predict as tree_predict, trees_equal
from repro.infer import forest as F
from repro.infer.forest import Forest

IMPLS = ("ref", "vmap", "pallas")


def _bootstrap_trees(ds, rng, n_trees=4, cfg=GrowConfig()):
    return [c45.build(ds.subset(rng.choice(ds.n_cases, ds.n_cases)), cfg)
            for _ in range(n_trees)]


@pytest.fixture
def ds(rng):
    return make_tree_dataset(rng, n=350, unknown_frac=0.15)


class TestPack:
    def test_shapes_and_live_prefixes(self, ds, rng):
        trees = _bootstrap_trees(ds, rng)
        fo = Forest.pack(trees)
        assert fo.n_trees == 4
        assert fo.capacity == max(t.size for t in trees)
        assert [int(n) for n in np.asarray(fo.n_nodes)] \
            == [t.size for t in trees]
        assert fo.n_levels == max(t.depth for t in trees) + 1

    def test_unpack_round_trips_each_tree(self, ds, rng):
        trees = _bootstrap_trees(ds, rng)
        fo = Forest.pack(trees)
        for i, t in enumerate(trees):
            back = fo.tree(i)
            # capacity differs (forest-wide padding); live prefix must match
            assert trees_equal(back, t)
            got = np.asarray(tree_predict(back, ds.x, ds.attr_is_cont))
            want = np.asarray(tree_predict(t, ds.x, ds.attr_is_cont))
            np.testing.assert_array_equal(got, want)

    def test_pack_rejects_mixed_classes_and_bad_weights(self, ds, rng):
        t2 = c45.build(ds, GrowConfig())
        t3 = c45.build(
            binning.fit([np.array([0, 1, 2])], np.array([0, 1, 2]),
                        attr_is_cont=[False], n_classes=3),
            GrowConfig())
        with pytest.raises(ValueError):
            Forest.pack([t2, t3])
        with pytest.raises(ValueError):
            Forest.pack([t2], weights=[1.0, 2.0])
        with pytest.raises(ValueError):
            Forest.pack([])


class TestOracleEquality:
    @pytest.mark.parametrize("impl", IMPLS)
    def test_per_tree_equals_tree_predict(self, ds, rng, impl):
        """Every impl == per-tree core.tree.predict, unknowns included."""
        trees = _bootstrap_trees(ds, rng)
        fo = Forest.pack(trees)
        got = np.asarray(F.predict_per_tree(fo, ds.x, ds.attr_is_cont,
                                            impl=impl))
        want = np.stack([
            np.asarray(tree_predict(t, ds.x, ds.attr_is_cont))
            for t in trees])
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("impl", ("vmap", "pallas"))
    def test_discrete_and_wide_splits(self, rng, impl):
        """Discrete multiway splits and unknown routing survive packing."""
        xs, ys = [], []
        for v in range(11):
            reps = 40 if v == 9 else 4
            xs += [v] * reps
            ys += [1 if v == 9 else v % 2] * reps
        ds = binning.fit([np.array(xs)], np.array(ys),
                         attr_is_cont=[False], n_classes=2)
        tree = c45.build(ds, GrowConfig(min_objs=1.0))
        fo = Forest.pack([tree, tree])
        probe = np.array([[3], [9], [-1]], np.int32)   # known, heavy, unknown
        got = np.asarray(F.predict_per_tree(fo, probe, ds.attr_is_cont,
                                            impl=impl))
        want = np.asarray(tree_predict(tree, probe, ds.attr_is_cont))
        np.testing.assert_array_equal(got[0], want)
        np.testing.assert_array_equal(got[1], want)
        assert got[0][2] == 1              # unknown followed the heavy child

    def test_single_tree_forest_is_identity(self, ds, rng):
        tree = c45.build(ds, GrowConfig())
        fo = Forest.pack([tree])
        for impl in IMPLS:
            got = np.asarray(F.predict(fo, ds.x, ds.attr_is_cont, impl=impl))
            want = np.asarray(tree_predict(tree, ds.x, ds.attr_is_cont))
            np.testing.assert_array_equal(got, want)

    def test_unknown_impl_rejected(self, ds, rng):
        fo = Forest.pack([c45.build(ds, GrowConfig())])
        with pytest.raises(ValueError):
            F.predict_per_tree(fo, ds.x, ds.attr_is_cont, impl="cuda")


class TestVoting:
    def test_weighted_vote_tally(self):
        per_tree = np.array([[0, 1], [0, 1], [1, 0]], np.int32)
        majority = np.asarray(F.vote(per_tree, np.ones(3, np.float32),
                                     n_classes=2))
        np.testing.assert_array_equal(majority, [0, 1])
        # one dominant tree flips the vote
        skewed = np.asarray(F.vote(per_tree,
                                   np.array([1.0, 1.0, 5.0], np.float32),
                                   n_classes=2))
        np.testing.assert_array_equal(skewed, [1, 0])

    def test_ensemble_vote_consistent_across_impls(self, ds, rng):
        trees = _bootstrap_trees(ds, rng, n_trees=5)
        fo = Forest.pack(trees, weights=rng.uniform(0.5, 2.0, 5))
        preds = {impl: np.asarray(F.predict(fo, ds.x, ds.attr_is_cont,
                                            impl=impl))
                 for impl in IMPLS}
        np.testing.assert_array_equal(preds["ref"], preds["vmap"])
        np.testing.assert_array_equal(preds["ref"], preds["pallas"])


@functools.lru_cache(maxsize=None)
def _standin_trees(name):
    """8 bootstrap trees of a stand-in, grown by the frontier engine, and
    each one's ``tree.predict`` of the stand-in's cases."""
    from repro.ensemble import ForestConfig, trainer
    ds = standin(name)
    fc = ForestConfig(n_trees=8, seed=0,
                      grow=GrowConfig(max_nodes=4096, frontier_slots=32))
    trees = trainer.train_forest_sequential(ds, fc, impl="frontier")
    return trees, np.stack([
        np.asarray(tree_predict(t, ds.x, ds.attr_is_cont)) for t in trees])


@pytest.mark.parametrize("n_trees", [1, 8])
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("name", STANDIN_NAMES)
def test_forest_predict_matches_tree_vote(name, impl, n_trees):
    """Every impl == a float64 weighted vote over per-tree tree.predict.

    The weights are small integers, so every tally is exact in float32 as
    in float64 and ties break alike (to the lowest class)."""
    ds = standin(name)
    trees, per_tree = _standin_trees(name)
    trees, per_tree = trees[:n_trees], per_tree[:n_trees]
    weights = np.arange(n_trees) % 3 + 1.0
    fo = Forest.pack(trees, weights=weights)
    tally = np.zeros((ds.n_cases, ds.n_classes))
    for row, wt in zip(per_tree, weights):
        tally[np.arange(ds.n_cases), row] += wt
    got = np.asarray(F.predict(fo, ds.x, ds.attr_is_cont, impl=impl))
    np.testing.assert_array_equal(got, tally.argmax(-1))
    if n_trees == 1:
        np.testing.assert_array_equal(got, per_tree[0])


class TestPersistenceRoundTrip:
    def test_pack_save_load_predictions_bit_identical(self, ds, rng,
                                                      tmp_path):
        """pack -> publish -> load: predictions == per-tree tree.predict."""
        from repro.infer import registry
        trees = _bootstrap_trees(ds, rng)
        fo = Forest.pack(trees)
        path = registry.publish(str(tmp_path), "m", fo)
        loaded, manifest = registry.load(path)
        assert manifest["n_trees"] == 4
        assert manifest["capacity"] == fo.capacity
        for impl in IMPLS:
            got = np.asarray(F.predict_per_tree(
                loaded, ds.x, ds.attr_is_cont, impl=impl))
            want = np.stack([
                np.asarray(tree_predict(t, ds.x, ds.attr_is_cont))
                for t in trees])
            np.testing.assert_array_equal(got, want)
