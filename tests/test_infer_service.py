"""Microbatching predict service: coalescing, scheduling, failover, obs."""

import numpy as np
import pytest
from conftest import make_tree_dataset, run_with_timeout

from repro.core import c45
from repro.core.config import GrowConfig
from repro.core.faults import ChaosReplica
from repro.infer import registry
from repro.infer.forest import Forest
from repro.infer.service import (BatchPredictService, InferReplica,
                                 PredictRequest, _Batch)
from repro.obs.metrics import Registry
from repro.obs.trace import Tracer


@pytest.fixture
def ds(rng):
    return make_tree_dataset(rng, n=300, unknown_frac=0.1)


@pytest.fixture
def fo(ds):
    return Forest.pack([c45.build(ds, GrowConfig())])


def _submit(svc, ds, n, start=0):
    for u in range(start, start + n):
        svc.submit(PredictRequest(uid=u, x_row=ds.x[u % ds.n_cases]))


def _expected(ds, fo, uids):
    from repro.infer.forest import predict
    labels = np.asarray(predict(fo, ds.x, ds.attr_is_cont))
    return {u: int(labels[u % ds.n_cases]) for u in uids}


class FlakyReplica(InferReplica):
    """Dies (tick raises) after serving ``fail_after`` batches."""

    def __init__(self, *a, fail_after=1, **kw):
        super().__init__(*a, **kw)
        self.fail_after = fail_after
        self.served = 0

    def tick(self):
        if self.queue and self.served >= self.fail_after:
            raise RuntimeError("injected replica death")
        out = super().tick()
        if out[0]:
            self.served += 1
        return out


class TestMicrobatching:
    def test_full_batches_close_immediately(self, ds, fo):
        reg = Registry()
        svc = BatchPredictService(
            [InferReplica.from_forest(fo, ds.attr_is_cont)],
            max_batch=32, max_wait_ticks=50, metrics=reg)
        _submit(svc, ds, 64)
        res = run_with_timeout(svc.run_until_drained)
        assert len(res) == 64 and not svc.failed
        hist = reg.get("infer_batch_rows")._snapshot_series()[0]
        assert hist["count"] == 2          # two full 32-row batches
        assert hist["sum"] == 64
        # nothing waited for the age-out path
        assert svc.stats()["ticks"] < 50

    def test_stragglers_age_out_after_max_wait(self, ds, fo):
        svc = BatchPredictService(
            [InferReplica.from_forest(fo, ds.attr_is_cont)],
            max_batch=64, max_wait_ticks=3)
        _submit(svc, ds, 10)               # far below max_batch
        res = run_with_timeout(svc.run_until_drained)
        assert len(res) == 10 and not svc.failed
        assert all(r.batch_size == 10 for r in res)

    def test_labels_match_direct_forest_predict(self, ds, fo):
        svc = BatchPredictService(
            [InferReplica.from_forest(fo, ds.attr_is_cont) for _ in range(3)],
            max_batch=16, max_wait_ticks=2)
        _submit(svc, ds, 100)
        res = run_with_timeout(svc.run_until_drained)
        want = _expected(ds, fo, range(100))
        assert len(res) == 100
        for r in res:
            assert r.label == want[r.uid], r

    @pytest.mark.parametrize("policy", ("ws", "drr", "od", "health_ws"))
    def test_every_policy_drains(self, ds, fo, policy):
        svc = BatchPredictService(
            [InferReplica.from_forest(fo, ds.attr_is_cont) for _ in range(3)],
            policy=policy, max_batch=8, max_wait_ticks=2)
        _submit(svc, ds, 60)
        res = run_with_timeout(svc.run_until_drained)
        assert len(res) == 60 and not svc.failed

    def test_ws_spreads_batches(self, ds, fo):
        svc = BatchPredictService(
            [InferReplica.from_forest(fo, ds.attr_is_cont) for _ in range(4)],
            policy="ws", max_batch=8, max_wait_ticks=1)
        _submit(svc, ds, 160)
        res = run_with_timeout(svc.run_until_drained)
        used = {r.replica for r in res}
        assert used == {0, 1, 2, 3}


class TestFailover:
    def test_replica_death_requeues_and_drains(self, ds, fo):
        reg = Registry()
        replicas = [
            FlakyReplica.from_forest(fo, ds.attr_is_cont),
            InferReplica.from_forest(fo, ds.attr_is_cont),
        ]
        replicas[0] = FlakyReplica(replicas[0].models, fail_after=1)
        svc = BatchPredictService(replicas, max_batch=8, max_wait_ticks=1,
                                  metrics=reg)
        _submit(svc, ds, 80)
        res = run_with_timeout(svc.run_until_drained)
        # every request terminal: served (possibly after requeue) or failed
        assert len(res) + len(svc.failed) == 80
        assert len(res) == 80              # healthy replica absorbed it all
        assert svc.stats()["evicted_replicas"] == [0]
        assert reg.get("infer_evictions_total").value() == 1
        # correctness survives the failover
        want = _expected(ds, fo, range(80))
        assert all(r.label == want[r.uid] for r in res)

    def test_all_replicas_dead_fails_explicitly(self, ds, fo):
        replicas = [FlakyReplica(
            InferReplica.from_forest(fo, ds.attr_is_cont).models,
            fail_after=0)]
        svc = BatchPredictService(replicas, max_batch=8, max_wait_ticks=1)
        _submit(svc, ds, 20)
        res = run_with_timeout(svc.run_until_drained)
        assert res == []
        assert len(svc.failed) == 20
        reasons = {f.reason for f in svc.failed}
        assert reasons <= {"no_replicas", "requeue_exhausted"}

    def test_requeue_budget_is_bounded(self, ds, fo):
        """A request cannot bounce between dying replicas forever."""
        replicas = [
            FlakyReplica(InferReplica.from_forest(fo, ds.attr_is_cont).models,
                         fail_after=0),
            FlakyReplica(InferReplica.from_forest(fo, ds.attr_is_cont).models,
                         fail_after=0),
        ]
        svc = BatchPredictService(replicas, max_batch=4, max_wait_ticks=1,
                                  max_requeues=1)
        _submit(svc, ds, 12)
        run_with_timeout(svc.run_until_drained)
        assert len(svc.failed) == 12
        assert svc.stats()["healthy_replicas"] == 0

    def test_eviction_masks_physical_indices(self, ds, fo):
        """After an eviction the policy still addresses the full list."""
        replicas = [
            FlakyReplica(InferReplica.from_forest(fo, ds.attr_is_cont).models,
                         fail_after=0),
            InferReplica.from_forest(fo, ds.attr_is_cont),
            InferReplica.from_forest(fo, ds.attr_is_cont),
        ]
        svc = BatchPredictService(replicas, policy="drr", max_batch=4,
                                  max_wait_ticks=1)
        _submit(svc, ds, 40)
        res = run_with_timeout(svc.run_until_drained)
        assert {r.replica for r in res} <= {1, 2}
        assert len(res) + len(svc.failed) == 40


def _chaos(fo, ds, *, max_batches=4, **kw):
    return ChaosReplica(InferReplica.from_forest(fo, ds.attr_is_cont,
                                                 max_batches=max_batches),
                        **kw)


def _queued_uids(rep):
    return [r.uid for b in rep.queue for r in b.requests]


def _round(svc):
    """One scheduling round of a tick, without serving anything."""
    svc._close_batches()
    svc._dispatch()


class TestSchedulingContract:
    """Policy, race and drain semantics over faulting replicas."""

    def test_health_ws_speed_fn_hook_steers_batches(self, ds, fo):
        reps = [_chaos(fo, ds, max_batches=8) for _ in range(2)]
        svc = BatchPredictService(reps, policy="health_ws",
                                  speed_fn=lambda: {0: 0.0, 1: 1.0},
                                  max_batch=4, max_wait_ticks=1)
        _submit(svc, ds, 16)
        _round(svc)
        assert _queued_uids(reps[0]) == []      # speed 0 = do not schedule
        assert _queued_uids(reps[1]) == list(range(16))
        res = run_with_timeout(svc.run_until_drained)
        assert {r.replica for r in res} == {1}
        want = _expected(ds, fo, range(16))
        assert sorted(r.uid for r in res) == list(range(16))
        assert all(r.label == want[r.uid] for r in res)

    def test_od_admits_at_most_one_batch_per_replica_per_tick(self, ds, fo):
        reps = [_chaos(fo, ds) for _ in range(2)]
        svc = BatchPredictService(reps, policy="od", max_batch=2,
                                  max_wait_ticks=0)
        admitted = []
        for i, rep in enumerate(reps):
            def admit(batch, i=i, inner=rep.admit):
                admitted.append((svc._tick, i))
                return inner(batch)
            rep.admit = admit
        _submit(svc, ds, 16)                    # 8 two-row batches
        _round(svc)
        assert [rep.queue_len() for rep in reps] == [1, 1]
        assert len(svc.ready) == 6
        res = run_with_timeout(svc.run_until_drained)
        assert sorted(r.uid for r in res) == list(range(16))
        assert svc.failed == []
        per_tick = {}
        for key in admitted:
            per_tick[key] = per_tick.get(key, 0) + 1
        assert len(admitted) == 8 and max(per_tick.values()) == 1

    def test_od_respects_free_queue_slots(self, ds, fo):
        rep = _chaos(fo, ds, max_batches=1)
        svc = BatchPredictService([rep], policy="od", max_batch=4,
                                  max_wait_ticks=0)
        _submit(svc, ds, 8)                     # two 4-row batches
        _round(svc)
        assert _queued_uids(rep) == [0, 1, 2, 3]
        _round(svc)                             # still full, a fresh round
        assert _queued_uids(rep) == [0, 1, 2, 3]
        assert len(svc.ready) == 1
        res = run_with_timeout(svc.run_until_drained)
        assert sorted(r.uid for r in res) == list(range(8))

    def test_drr_rotation_addresses_physical_replicas_after_eviction(
            self, ds, fo):
        reps = [_chaos(fo, ds, max_batches=8) for _ in range(3)]
        svc = BatchPredictService(reps, policy="drr", max_batch=1,
                                  max_wait_ticks=0)
        _submit(svc, ds, 2)
        _round(svc)                  # DRR: -> r0, r1; the pointer is at r2
        assert [_queued_uids(r) for r in reps] == [[0], [1], []]
        svc._evict(0, "test")        # requeues uid 0 at the front
        _submit(svc, ds, 1, start=2)
        _round(svc)
        # the pointer means physical replica 2, and the rotation then skips
        # the evicted r0
        assert _queued_uids(reps[2]) == [0]
        assert _queued_uids(reps[1]) == [1, 2]
        res = run_with_timeout(svc.run_until_drained)
        assert sorted(r.uid for r in res) == [0, 1, 2]
        assert {r.replica for r in res} == {1, 2}

    def test_drr_stays_fair_across_eviction(self, ds, fo):
        reps = [_chaos(fo, ds, max_batches=16) for _ in range(3)]
        svc = BatchPredictService(reps, policy="drr", max_batch=1,
                                  max_wait_ticks=0)
        svc._evict(1, "test")
        _submit(svc, ds, 8)
        _round(svc)
        assert _queued_uids(reps[1]) == []
        assert len(_queued_uids(reps[0])) == len(_queued_uids(reps[2])) == 4

    def test_admit_race_requeues_instead_of_crashing(self, ds, fo):
        tracer = Tracer(enabled=True)
        rep = _chaos(fo, ds, admit_failures=2)
        svc = BatchPredictService([rep], max_batch=4, max_wait_ticks=1,
                                  max_requeues=0, tracer=tracer)
        _submit(svc, ds, 12)
        res = run_with_timeout(svc.run_until_drained)
        assert sorted(r.uid for r in res) == list(range(12))
        assert svc.failed == []
        assert svc.healthy == [True]            # a race is not a death
        assert svc.stats()["requeues"] == 0     # and costs no budget
        races = [e for e in tracer._events
                 if e.get("name") == "infer.batch.race"]
        assert len(races) == 2

    def test_max_ticks_reports_undrained_requests(self, ds, fo):
        svc = BatchPredictService([_chaos(fo, ds, max_batches=1)],
                                  max_batch=2, max_wait_ticks=0)
        _submit(svc, ds, 10)                    # 5 batches, 1 served a tick
        res = svc.run_until_drained(max_ticks=2)
        assert len(res) == 4
        assert sorted([r.uid for r in res] + [f.uid for f in svc.failed]) \
            == list(range(10))
        assert {f.reason for f in svc.failed} == {"max_ticks"}
        assert len(svc.failed) == 6

    def test_eviction_records_instant_and_metric(self, ds, fo):
        tracer = Tracer(enabled=True)
        reg = Registry()
        reps = [_chaos(fo, ds, fail_at_tick=2), _chaos(fo, ds)]
        svc = BatchPredictService(reps, policy="drr", max_batch=4,
                                  max_wait_ticks=1, tracer=tracer,
                                  metrics=reg)
        _submit(svc, ds, 32)
        res = run_with_timeout(svc.run_until_drained)
        evicts = [e for e in tracer._events
                  if e.get("name") == "infer.replica.evict"]
        assert [e["args"]["replica"] for e in evicts] == [0]
        assert "injected replica death" in evicts[0]["args"]["detail"]
        assert reg.get("infer_evictions_total").value() == 1
        assert svc.healthy == [False, True]
        want = _expected(ds, fo, range(32))
        assert sorted(r.uid for r in res) == list(range(32))
        assert all(r.label == want[r.uid] for r in res)

    def test_failure_breakdown_stats(self, ds, fo):
        svc = BatchPredictService([_chaos(fo, ds, fail_at_tick=1)],
                                  max_batch=4, max_wait_ticks=1)
        _submit(svc, ds, 8)
        run_with_timeout(svc.run_until_drained)
        s = svc.stats()
        assert s["results"] == 0 and s["failed"] == 8
        assert sum(s["failed_by_reason"].values()) == 8
        assert s["evicted_replicas"] == [0]


class TestCanaryShadow:
    def _handle(self, tmp_path, ds, rng):
        """Stable = newest publish (a deliberately degenerate depth-1 tree);
        candidate = the prior full-depth tree, so the two arms disagree."""
        full = c45.build(ds, GrowConfig())
        stump = c45.build(ds, GrowConfig(max_depth=1))
        v1 = registry.publish(str(tmp_path), "m", full)
        registry.publish(str(tmp_path), "m", stump)
        handle = registry.ModelHandle(str(tmp_path), "m")
        return handle, v1

    def test_canary_arm_served_by_canary_model(self, tmp_path, ds, rng):
        handle, cand = self._handle(tmp_path, ds, rng)
        handle.set_canary(cand, 0.5)
        svc = BatchPredictService(
            [InferReplica.from_handle(handle, ds.attr_is_cont)],
            handle=handle, max_batch=8, max_wait_ticks=1)
        _submit(svc, ds, 120)
        res = run_with_timeout(svc.run_until_drained)
        assert len(res) == 120
        by_arm = {a: [r for r in res if r.arm == a]
                  for a in ("stable", "canary")}
        assert by_arm["stable"] and by_arm["canary"]
        want_stable = _expected(ds, handle.stable, range(120))
        want_canary = _expected(ds, handle.canary, range(120))
        assert all(r.label == want_stable[r.uid] for r in by_arm["stable"])
        assert all(r.label == want_canary[r.uid] for r in by_arm["canary"])
        # routing is the handle's deterministic hash
        assert all(handle.route(r.uid) == r.arm for r in res)

    def test_shadow_mirrors_without_shifting(self, tmp_path, ds, rng):
        handle, cand = self._handle(tmp_path, ds, rng)
        handle.set_canary(cand, 0.5, shadow=True)
        reg = Registry()
        svc = BatchPredictService(
            [InferReplica.from_handle(handle, ds.attr_is_cont)],
            handle=handle, max_batch=16, max_wait_ticks=1, metrics=reg)
        _submit(svc, ds, 64)
        res = run_with_timeout(svc.run_until_drained)
        assert len(res) == 64
        assert all(r.arm == "stable" for r in res)      # no traffic shift
        assert reg.get("infer_shadow_mirrored_total").value() == 64
        # the degenerate shadow model must disagree somewhere
        assert reg.get("infer_shadow_disagree_total").value() > 0

    def test_hot_swap_reaches_replicas(self, tmp_path, ds, rng):
        handle, cand = self._handle(tmp_path, ds, rng)
        rep = InferReplica.from_handle(handle, ds.attr_is_cont)
        svc = BatchPredictService([rep], handle=handle, max_batch=8,
                                  max_wait_ticks=1)
        _submit(svc, ds, 16)
        run_with_timeout(svc.run_until_drained)
        handle.set_canary(cand, 0.0)
        handle.promote_canary()            # in-memory hot swap
        want_new = _expected(ds, handle.stable, range(16))
        svc2 = BatchPredictService([rep], handle=handle, max_batch=8,
                                   max_wait_ticks=1)
        _submit(svc2, ds, 16)
        res = run_with_timeout(svc2.run_until_drained)
        assert all(r.label == want_new[r.uid] for r in res)


class TestObservability:
    def test_metrics_and_spans(self, ds, fo):
        reg = Registry()
        tracer = Tracer(enabled=True)
        svc = BatchPredictService(
            [InferReplica.from_forest(fo, ds.attr_is_cont) for _ in range(2)],
            max_batch=8, max_wait_ticks=2, metrics=reg, tracer=tracer)
        _submit(svc, ds, 40)
        res = run_with_timeout(svc.run_until_drained)
        assert len(res) == 40
        assert reg.get("infer_requests_total").value() == 40
        assert reg.get("infer_results_total").value(arm="stable") == 40
        wait = reg.get("infer_queue_wait_ticks")._snapshot_series()[0]
        assert wait["count"] == 40
        busy = reg.get("infer_replica_batches_total")
        assert sum(s["value"] for s in busy._snapshot_series()) >= 5
        names = {e.get("name") for e in tracer._events}
        assert {"predict", "infer.tick", "infer.batch.dispatch"} <= names

    def test_accounting_identity(self, ds, fo):
        """submitted == results + failed, always (the drain contract)."""
        reg = Registry()
        replicas = [
            FlakyReplica(InferReplica.from_forest(fo, ds.attr_is_cont).models,
                         fail_after=2),
            InferReplica.from_forest(fo, ds.attr_is_cont),
        ]
        svc = BatchPredictService(replicas, max_batch=8, max_wait_ticks=1,
                                  metrics=reg)
        _submit(svc, ds, 120)
        run_with_timeout(svc.run_until_drained)
        assert len(svc.results) + len(svc.failed) == 120

    def test_replica_rejects_unknown_arm(self, ds, fo):
        rep = InferReplica.from_forest(fo, ds.attr_is_cont)
        with pytest.raises(KeyError):
            rep.admit(_Batch(arm="canary", requests=[
                PredictRequest(uid=0, x_row=ds.x[0])]))
