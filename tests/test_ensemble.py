"""Ensemble trainer: farm determinism, chaos acceptance, OOB, publishing.

The load-bearing guarantees:

  * the forest is a pure function of ``(dataset, ForestConfig)`` — worker
    count, scheduling order and injected chaos cannot change a bit of it
    (tree tasks are pure in ``(seed, tree_id)``, results keyed by id);
  * both growth engines (per-tree c45 oracle, jitted frontier superstep)
    grow identical trees from the same bootstrap weights + feature mask;
  * the acceptance flow: chaos-trained forest == sequential oracle, finite
    OOB score recorded at publish, and the published version serves
    predictions through ``infer.service`` that match
    ``Forest.predict(impl="ref")``.
"""

import numpy as np
import pytest

from conftest import (STANDIN_NAMES, make_tree_dataset, run_with_timeout,
                      standin)
from repro.core import faults
from repro.core.config import GrowConfig
from repro.core.farm import FaultPolicy
from repro.core.tree import trees_equal
from repro.ensemble import (ForestConfig, QuarantinedTrees, oob, publish,
                            sampling, trainer)
from repro.infer import forest as F
from repro.infer import registry
from repro.infer.service import (BatchPredictService, InferReplica,
                                 PredictRequest)
from repro.obs.metrics import Registry

pytestmark = pytest.mark.timeout(300)

GROW = GrowConfig(max_nodes=1 << 12)


def _dataset(seed=0, n=300, **kw):
    rng = np.random.default_rng(seed)
    kw.setdefault("n_cont", 2)
    kw.setdefault("n_disc", 2)
    kw.setdefault("n_classes", 3)
    return make_tree_dataset(rng, n, **kw)


def _forests_equal(a, b):
    return len(a) == len(b) and all(trees_equal(x, y) for x, y in zip(a, b))


# ------------------------------------------------------------------ sampling

class TestSampling:
    def test_pure_in_seed_and_tree_id(self):
        a = sampling.draw(3, 5, n_cases=100, n_attrs=7)
        b = sampling.draw(3, 5, n_cases=100, n_attrs=7)
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.attr_mask, b.attr_mask)
        c = sampling.draw(3, 6, n_cases=100, n_attrs=7)
        assert not np.array_equal(a.counts, c.counts) \
            or not np.array_equal(a.attr_mask, c.attr_mask)

    def test_bootstrap_preserves_total_draws(self):
        counts = sampling.bootstrap_counts(0, 0, 500)
        assert counts.sum() == 500
        assert (counts == 0).any()          # ~36.8% of cases are OOB

    def test_feature_mask_size_and_bounds(self):
        m = sampling.feature_mask(0, 0, 9)
        assert m.sum() == sampling.default_mtry(9) == 3
        assert sampling.feature_mask(0, 0, 9, mtry=9).all()
        with pytest.raises(ValueError):
            sampling.feature_mask(0, 0, 9, mtry=10)
        with pytest.raises(ValueError):
            sampling.feature_mask(0, 0, 9, mtry=0)

    def test_no_bootstrap_keeps_base_weights(self):
        s = sampling.draw(0, 0, n_cases=10, n_attrs=3, bootstrap=False,
                          base_w=np.full(10, 2.0, np.float32))
        np.testing.assert_array_equal(s.case_w, np.full(10, 2.0))
        assert not s.oob.any()


# ------------------------------------------------------- farm determinism

class TestFarmDeterminism:
    def test_forest_identical_across_worker_counts(self):
        ds = _dataset()
        fc = ForestConfig(n_trees=5, seed=2, grow=GROW)
        seq = trainer.train_forest_sequential(ds, fc)
        for n_workers in (1, 4):
            res = run_with_timeout(
                lambda: trainer.train_forest(ds, fc, n_workers=n_workers),
                120)
            assert res.tree_ids == list(range(5))
            assert _forests_equal(seq, res.trees), \
                f"forest diverged at n_workers={n_workers}"

    def test_chaos_run_equals_oracle(self):
        """Acceptance: crash_p=0.2 + a permanently dead worker -> identical
        forest, with real retries exercised."""
        ds = _dataset()
        fc = ForestConfig(n_trees=8, seed=0, grow=GROW)
        seq = trainer.train_forest_sequential(ds, fc)
        inj = faults.FaultInjector(
            seed=7, spec=faults.FaultSpec(
                crash_p=0.2, dead_workers=frozenset({1})),
            key_fn=lambda tid: tid)
        stats = {}
        res = run_with_timeout(
            lambda: trainer.train_forest(
                ds, fc, n_workers=4, injector=inj,
                fault=FaultPolicy(max_retries=8, seed=3, backoff_base=1e-4),
                stats_out=stats), 240)
        assert _forests_equal(seq, res.trees), \
            "chaos forest diverged from the sequential oracle"
        assert stats["dead_workers"] == [1]
        assert stats["failures"] > 0 and stats["retries"] > 0
        assert stats["quarantined"] == 0 and not res.quarantined

    def test_frontier_impl_matches_c45(self):
        ds = _dataset(seed=4)
        fc = ForestConfig(n_trees=4, seed=5, grow=GROW)
        seq = trainer.train_forest_sequential(ds, fc, impl="c45")
        fro = trainer.train_forest_sequential(ds, fc, impl="frontier")
        assert _forests_equal(seq, fro)

    @pytest.mark.parametrize("name", STANDIN_NAMES)
    def test_farm_trained_frontier_forest_equals_sequential_oracle(self,
                                                                  name):
        """The farm's frontier-grown forest == the per-tree c45 oracle's,
        bootstrap weights and feature masks included."""
        ds = standin(name)
        fc = ForestConfig(n_trees=4, seed=1,
                          grow=GrowConfig(max_nodes=4096, frontier_slots=32))
        seq = trainer.train_forest_sequential(ds, fc, impl="c45")
        res = run_with_timeout(
            lambda: trainer.train_forest(ds, fc, impl="frontier",
                                         n_workers=2), 240)
        assert _forests_equal(seq, res.trees)

    def test_farm_build_engine_accepts_same_hooks(self):
        """All three engines share the attr_mask/case_w contract."""
        from repro.core import farm_build
        ds = _dataset(seed=8, n=200)
        s = sampling.draw(0, 0, n_cases=ds.n_cases, n_attrs=ds.n_attrs,
                          base_w=ds.w)
        from repro.core import c45
        want = c45.build(ds, GROW, attr_mask=s.attr_mask, case_w=s.case_w)
        got = run_with_timeout(
            lambda: farm_build.build(ds, GROW, n_workers=3,
                                     attr_mask=s.attr_mask,
                                     case_w=s.case_w), 120)
        assert trees_equal(want, got)

    def test_feature_mask_actually_restricts_splits(self):
        ds = _dataset(seed=1)
        fc = ForestConfig(n_trees=4, seed=3, mtry=1, grow=GROW)
        for tid, tree in enumerate(trainer.train_forest_sequential(ds, fc)):
            mask = sampling.feature_mask(fc.seed, tid, ds.n_attrs, 1)
            used = np.asarray(tree.node_attr)[:tree.size]
            used = set(used[used >= 0].tolist())
            allowed = set(np.nonzero(mask)[0].tolist())
            assert used <= allowed, f"tree {tid} split outside its subset"

    def test_strict_quarantine_raises_nonstrict_drops(self):
        ds = _dataset(seed=6, n=150)
        fc = ForestConfig(n_trees=3, seed=1, grow=GROW)
        # tree 1 poisoned: crashes on every attempt
        inj = faults.FaultInjector(
            seed=0, spec=faults.FaultSpec(crash_p=1.0),
            key_fn=lambda tid: "poison" if tid == 1 else f"ok{tid}")
        inj.decide = lambda key, call, _d=inj.decide: \
            "crash" if key == "poison" else "ok"
        fault = FaultPolicy(max_retries=1, backoff_base=0.0)
        with pytest.raises(QuarantinedTrees):
            run_with_timeout(
                lambda: trainer.train_forest(ds, fc, n_workers=2,
                                             injector=inj, fault=fault), 120)
        inj2 = faults.FaultInjector(
            seed=0, spec=faults.FaultSpec(crash_p=1.0),
            key_fn=lambda tid: "poison" if tid == 1 else f"ok{tid}")
        inj2.decide = lambda key, call: \
            "crash" if key == "poison" else "ok"
        res = run_with_timeout(
            lambda: trainer.train_forest(ds, fc, n_workers=2, injector=inj2,
                                         fault=fault, strict=False), 120)
        assert res.quarantined == [1]
        assert res.tree_ids == [0, 2]
        seq = trainer.train_forest_sequential(ds, fc)
        assert trees_equal(res.trees[0], seq[0])
        assert trees_equal(res.trees[1], seq[2])

    def test_trainer_metrics_and_spans(self):
        from repro.obs.trace import Tracer
        ds = _dataset(seed=2, n=150)
        fc = ForestConfig(n_trees=3, seed=0, grow=GROW)
        reg = Registry()
        tracer = Tracer()
        run_with_timeout(
            lambda: trainer.train_forest(ds, fc, n_workers=2, metrics=reg,
                                         tracer=tracer), 120)
        assert reg.get("ensemble_trees_trained_total").value(impl="c45") == 3
        assert reg.get("ensemble_trees_per_s").value(impl="c45") > 0
        names = {e.get("name") for e in tracer.events}
        assert "ensemble.tree" in names


# ------------------------------------------------------------------- OOB

class TestOOB:
    def test_oob_score_finite_and_bounded(self):
        ds = _dataset()
        fc = ForestConfig(n_trees=8, seed=0, grow=GROW)
        res = run_with_timeout(
            lambda: trainer.train_forest(ds, fc, n_workers=2), 120)
        r = oob.oob_score(res.trees, ds, fc, tree_ids=res.tree_ids)
        assert np.isfinite(r.score) and 0.0 <= r.score <= 1.0
        assert r.coverage > 0.5
        assert r.pred.shape == (ds.n_cases,)
        covered = r.pred >= 0
        assert covered.sum() == r.n_covered

    def test_oob_ignores_in_bag_trees(self):
        """A case's OOB vote must only see trees whose bootstrap missed it."""
        ds = _dataset(seed=3, n=200)
        fc = ForestConfig(n_trees=5, seed=7, grow=GROW)
        trees = trainer.train_forest_sequential(ds, fc)
        m = oob.oob_matrix(fc, ds.n_cases)
        for t in range(fc.n_trees):
            counts = sampling.bootstrap_counts(fc.seed, t, ds.n_cases)
            np.testing.assert_array_equal(m[t], counts == 0)
        r = oob.oob_score(trees, ds, fc)
        uncovered = ~m.any(axis=0)
        assert (r.pred[uncovered] == -1).all()

    def test_oob_requires_bootstrap(self):
        ds = _dataset(n=100)
        fc = ForestConfig(n_trees=2, seed=0, bootstrap=False, grow=GROW)
        trees = trainer.train_forest_sequential(ds, fc)
        with pytest.raises(ValueError, match="bootstrap"):
            oob.oob_score(trees, ds, fc)

    def test_permutation_importance_flags_signal_column(self):
        # Build a dataset whose label is a noisy threshold of column 0 (the
        # conftest generator keeps y marginally uniform, i.e. signal-free);
        # permuting col 0 must hurt far more than the noise columns.
        from repro.core import binning
        rng = np.random.default_rng(0)
        n = 500
        c0 = rng.uniform(-2, 2, n)
        noise = [rng.uniform(-2, 2, n), rng.integers(0, 3, n)]
        y = (c0 > 0).astype(np.int64)
        y = np.where(rng.random(n) < 0.1, 1 - y, y)    # 10% label noise
        ds = binning.fit([c0, *noise], y,
                         attr_is_cont=[True, True, False], n_classes=2,
                         max_bins=32)
        fc = ForestConfig(n_trees=12, seed=2, mtry=2, grow=GROW)
        trees = trainer.train_forest_sequential(ds, fc)
        imp = oob.permutation_importance(trees, ds, fc, n_repeats=2)
        assert imp.shape == (ds.n_attrs,)
        assert imp[0] == imp.max()
        assert imp[0] > 0

    def test_permutation_importance_is_deterministic(self):
        ds = _dataset(seed=5, n=200)
        fc = ForestConfig(n_trees=4, seed=1, grow=GROW)
        trees = trainer.train_forest_sequential(ds, fc)
        a = oob.permutation_importance(trees, ds, fc)
        b = oob.permutation_importance(trees, ds, fc)
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- publish + serving

class TestPublishServe:
    def test_acceptance_chaos_train_publish_serve(self, tmp_path):
        """The issue's acceptance flow, end to end: chaos-trained forest ==
        oracle, finite OOB in the manifest, registry round-trip through
        infer.service matching Forest.predict(impl="ref")."""
        ds = _dataset()
        fc = ForestConfig(n_trees=6, seed=1, grow=GROW)
        seq = trainer.train_forest_sequential(ds, fc)
        inj = faults.FaultInjector(
            seed=7, spec=faults.FaultSpec(
                crash_p=0.2, dead_workers=frozenset({1})),
            key_fn=lambda tid: tid)
        stats = {}
        res = run_with_timeout(
            lambda: trainer.train_forest(
                ds, fc, n_workers=4, injector=inj,
                fault=FaultPolicy(max_retries=8, backoff_base=1e-4),
                stats_out=stats), 240)
        assert _forests_equal(seq, res.trees)
        assert stats["dead_workers"] == [1]

        path = publish.publish_forest(str(tmp_path), "rf", res, ds)
        meta = registry.manifest_of(path)["metadata"]
        assert np.isfinite(meta["oob_score"])
        assert meta["seed"] == 1 and meta["n_trees"] == 6
        assert meta["mtry"] == fc.resolved_mtry(ds.n_attrs)

        loaded, _ = registry.load(path)
        want = np.asarray(F.predict(loaded, ds.x, ds.attr_is_cont,
                                    impl="ref"))
        handle = registry.ModelHandle(str(tmp_path), "rf")
        svc = BatchPredictService(
            [InferReplica.from_handle(handle, ds.attr_is_cont)
             for _ in range(2)],
            handle=handle, max_batch=64, metrics=Registry())
        n = ds.n_cases
        for uid in range(n):
            svc.submit(PredictRequest(uid=uid, x_row=ds.x[uid]))
        results = run_with_timeout(svc.run_until_drained, 120)
        assert len(results) == n and not svc.failed
        got = np.zeros(n, np.int64)
        for r in results:
            got[r.uid] = r.label
        np.testing.assert_array_equal(got, want)

    def test_publish_forest_metadata_without_oob(self, tmp_path):
        ds = _dataset(n=120)
        fc = ForestConfig(n_trees=2, seed=0, bootstrap=False, grow=GROW)
        res = run_with_timeout(
            lambda: trainer.train_forest(ds, fc, n_workers=1), 120)
        path = publish.publish_forest(str(tmp_path), "rf", res, ds)
        meta = registry.manifest_of(path)["metadata"]
        assert meta["bootstrap"] is False
        assert "oob_score" not in meta
        assert meta["tree_ids"] == [0, 1]
