"""Supervised farm fault paths: crash/retry/quarantine, deadlines, deaths.

Every test that exercises a termination guarantee runs under
``run_with_timeout`` so a supervision regression *fails* instead of hanging
the suite (the pre-supervision farm deadlocked forever on a single worker
exception).  ``pytest.mark.timeout`` is applied as a second backstop for
environments with pytest-timeout installed.
"""

import threading
import time

import pytest

from conftest import run_with_timeout
from repro.core import faults
from repro.core.farm import (AllWorkersDead, Farm, FaultPolicy, TaskFailure,
                             WorkerCrashed)
from repro.core.scheduler import OD, WS, HealthWS, QueueState
from repro.core.health import FarmHealth, HeartbeatMonitor, StragglerMonitor

pytestmark = pytest.mark.timeout(120)


def range_emitter(n):
    """Emitter that floods n tasks at start-up and collects results."""
    seen = []

    def emitter(task, send):
        if task is None:
            for i in range(n):
                send(i, weight=float(i + 1))
        else:
            seen.append(task)
    return emitter, seen


def results(seen):
    return sorted(x for x in seen if not isinstance(x, TaskFailure))


# ---------------------------------------------------------------------------
# deadlock regressions (satellite: the original farm hung on any exception)
# ---------------------------------------------------------------------------

def test_worker_exception_does_not_deadlock_run():
    """A crashing worker_svc must terminate the run, not hang feedback.get."""
    emitter, seen = range_emitter(10)

    def svc(x):
        if x == 4:
            raise ValueError("boom")
        return x

    farm = Farm(3, fault=FaultPolicy(max_retries=1, backoff_base=0.0))
    stats = run_with_timeout(lambda: farm.run(emitter, svc), 30)
    assert results(seen) == [x for x in range(10) if x != 4]
    assert stats["quarantined"] == 1
    assert stats["failures"] == 2          # initial attempt + 1 retry
    assert farm.quarantined[0].payload == 4


def test_send_out_aborts_when_all_workers_dead():
    """The full-queue spin in send_out must raise, not spin forever."""
    def svc(x):
        raise WorkerCrashed("gone")

    def emitter(task, send):
        if task is None:
            for i in range(10):
                send(i)

    farm = Farm(1, policy=OD(), fault=FaultPolicy(max_retries=3))
    with pytest.raises(AllWorkersDead):
        run_with_timeout(lambda: farm.run(emitter, svc), 30)


def test_zero_live_workers_raises_with_tasks_outstanding():
    emitter, _ = range_emitter(5)
    farm = Farm(2, fault=FaultPolicy(max_retries=4))
    with pytest.raises(AllWorkersDead):
        run_with_timeout(
            lambda: farm.run(emitter, lambda x: (_ for _ in ()).throw(
                WorkerCrashed("dead"))), 30)


# ---------------------------------------------------------------------------
# retry / backoff / quarantine
# ---------------------------------------------------------------------------

def test_retry_recovers_transient_crashes():
    attempts = {}
    lock = threading.Lock()

    def svc(x):
        with lock:
            attempts[x] = attempts.get(x, 0) + 1
            if attempts[x] == 1 and x % 3 == 0:
                raise RuntimeError(f"transient {x}")
        return x

    emitter, seen = range_emitter(12)
    farm = Farm(4, fault=FaultPolicy(max_retries=2, backoff_base=1e-4))
    stats = run_with_timeout(lambda: farm.run(emitter, svc), 30)
    assert results(seen) == list(range(12))
    assert stats["retries"] == 4           # 0, 3, 6, 9
    assert stats["quarantined"] == 0


def test_quarantine_after_budget_and_emitter_notified():
    emitter_fail = []

    def emitter(task, send):
        if task is None:
            send("poison")
            send("fine")
        elif isinstance(task, TaskFailure):
            emitter_fail.append(task)

    def svc(x):
        if x == "poison":
            raise RuntimeError("always")
        return x

    farm = Farm(2, fault=FaultPolicy(max_retries=2, quarantine_after=2,
                                     backoff_base=0.0))
    stats = run_with_timeout(lambda: farm.run(emitter, svc), 30)
    assert stats["quarantined"] == 1
    assert stats["failures"] == 2          # quarantine_after overrides
    assert emitter_fail[0].payload == "poison"
    assert "always" in emitter_fail[0].error


def test_backoff_is_bounded_and_jittered():
    import random
    pol = FaultPolicy(backoff_base=0.01, backoff_factor=2.0, backoff_max=0.05,
                      jitter=0.5)
    rng = random.Random(0)
    delays = [pol.backoff(k, rng) for k in range(1, 12)]
    assert all(0 < d <= 0.05 * 1.5 for d in delays)
    assert delays[1] != delays[2]          # jitter decorrelates
    assert FaultPolicy(backoff_base=0.0).backoff(3, rng) == 0.0


# ---------------------------------------------------------------------------
# deadlines (hung workers) and worker death
# ---------------------------------------------------------------------------

def test_deadline_declares_hung_worker_dead_and_redispatches():
    hung = threading.Event()

    def svc(x):
        if x == 5 and not hung.is_set():
            hung.set()
            time.sleep(3.0)                # >> deadline
        return x * 10

    emitter, seen = range_emitter(8)
    farm = Farm(3, fault=FaultPolicy(task_deadline=0.25, max_retries=3,
                                     backoff_base=1e-4))
    stats = run_with_timeout(lambda: farm.run(emitter, svc), 30)
    assert results(seen) == [x * 10 for x in range(8)]
    assert stats["timeouts"] >= 1
    assert len(stats["dead_workers"]) == 1


def test_worker_death_requeues_its_backlog():
    inj = faults.FaultInjector(seed=0, spec=faults.FaultSpec(
        dead_workers=frozenset({0})))
    emitter, seen = range_emitter(30)
    farm = Farm(3, fault=FaultPolicy(max_retries=2))
    stats = run_with_timeout(
        lambda: farm.run(emitter, inj.wrap_worker(lambda x: x)), 30)
    assert results(seen) == list(range(30))
    assert stats["dead_workers"] == [0]
    assert stats["n_live_workers"] == 2


def test_stats_expose_failure_breakdown():
    emitter, _ = range_emitter(4)
    farm = Farm(2)
    stats = run_with_timeout(lambda: farm.run(emitter, lambda x: x), 30)
    for key in ("failures", "retries", "requeues", "timeouts", "quarantined",
                "dead_workers", "n_live_workers", "emitter_busy",
                "worker_busy", "worker_tasks"):
        assert key in stats
    assert stats["failures"] == 0
    assert sum(stats["worker_tasks"]) == 4


# ---------------------------------------------------------------------------
# deterministic injection harness
# ---------------------------------------------------------------------------

def test_injector_is_deterministic_across_runs():
    spec = faults.FaultSpec(crash_p=0.3, die_p=0.1, hang_p=0.05, slow_p=0.2)
    a = faults.FaultInjector(seed=42, spec=spec)
    b = faults.FaultInjector(seed=42, spec=spec)
    keys = [(k, c) for k in range(50) for c in range(3)]
    assert [a.decide(k, c) for k, c in keys] == \
        [b.decide(k, c) for k, c in keys]
    c = faults.FaultInjector(seed=43, spec=spec)
    assert [a.decide(k, c_) for k, c_ in keys] != \
        [c.decide(k, c_) for k, c_ in keys]


def test_injector_rates_roughly_match_probabilities():
    spec = faults.FaultSpec(crash_p=0.25)
    inj = faults.FaultInjector(seed=1, spec=spec)
    n = 2000
    crashes = sum(inj.decide(k, 0) == "crash" for k in range(n))
    assert 0.18 < crashes / n < 0.32


def test_injector_probabilities_must_be_sane():
    with pytest.raises(ValueError):
        faults.FaultSpec(crash_p=0.7, hang_p=0.5)


# ---------------------------------------------------------------------------
# worker health: heartbeat + straggler weights into the scheduling path
# ---------------------------------------------------------------------------

def test_heartbeat_failure_detection():
    hb = HeartbeatMonitor(timeout=10.0)
    hb.beat("h0", 1, now=0.0)
    hb.beat("h1", 1, now=0.0)
    hb.beat("h0", 2, now=8.0)
    assert hb.failed(now=11.0) == ["h1"]
    assert hb.alive(now=11.0) == ["h0"]


def test_straggler_detection_and_ws_weights():
    sm = StragglerMonitor(factor=1.5)
    for _ in range(8):
        sm.record("fast0", 1.0)
        sm.record("fast1", 1.1)
        sm.record("slow", 2.5)
    assert sm.stragglers() == ["slow"]
    w = sm.ws_weights()
    assert w["slow"] < w["fast0"]          # slow host gets less work


def test_health_ws_biases_away_from_stragglers():
    health = FarmHealth(2)
    for _ in range(8):
        health.on_task(0, 1.0)    # w0: slow
        health.on_task(1, 0.1)    # w1: fast
    pol = health.policy()
    views = [QueueState(tasks=0, weight=1.0, cap=8),
             QueueState(tasks=0, weight=2.0, cap=8)]
    # plain WS would pick 0 (lower raw weight); health-WS picks the fast one
    assert WS().pick(1.0, views) == 0
    assert pol.pick(1.0, views) == 1


def test_health_ws_skips_dead_and_heartbeat_failed_workers():
    hb = HeartbeatMonitor(timeout=10.0)
    health = FarmHealth(3, heartbeat=hb)
    health.on_task(0, 0.1, now=0.0)
    health.on_task(1, 0.1, now=100.0)      # w0 is now 100s silent -> failed
    health.on_worker_dead(2)
    speeds = health.speeds(now=100.0)
    assert speeds[0] == 0.0 and speeds[2] == 0.0 and speeds[1] > 0
    pol = HealthWS(lambda: speeds)
    views = [QueueState(0, 0.0, 8), QueueState(5, 50.0, 8),
             QueueState(0, 0.0, 8)]
    assert pol.pick(1.0, views) == 1       # only healthy candidate wins
    # ...but if every healthy queue is full, fall back to raw WS capacity
    views_full = [QueueState(0, 0.0, 8), QueueState(8, 50.0, 8),
                  QueueState(0, 0.0, 8)]
    assert pol.pick(1.0, views_full) in (0, 2)


def test_farm_feeds_health_monitors():
    health = FarmHealth(2)
    emitter, seen = range_emitter(10)
    farm = Farm(2, health=health)
    run_with_timeout(lambda: farm.run(emitter, lambda x: x), 30)
    assert isinstance(farm.policy, HealthWS)
    assert results(seen) == list(range(10))
    assert set(health.straggler.times) <= {"w0", "w1"}
    assert len(health.heartbeat.hosts) >= 1


def test_farm_reports_dead_worker_to_health():
    health = FarmHealth(2)
    inj = faults.FaultInjector(seed=0, spec=faults.FaultSpec(
        dead_workers=frozenset({1})))
    emitter, seen = range_emitter(12)
    farm = Farm(2, health=health, fault=FaultPolicy(max_retries=2))
    run_with_timeout(
        lambda: farm.run(emitter, inj.wrap_worker(lambda x: x)), 30)
    assert health.dead == {1}
    assert health.speeds()[1] == 0.0
    assert results(seen) == list(range(12))
