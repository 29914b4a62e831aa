"""Observability layer: tracer, metrics registry, report, instrumented runs."""

import glob
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_tree_dataset

from repro.core import farm_build, frontier
from repro.core.config import GrowConfig
from repro.core.farm import FaultPolicy
from repro.core.faults import FaultInjector, FaultSpec
from repro.core.tree import trees_equal
from repro.data import datasets
from repro.obs import report
from repro.obs.metrics import DEFAULT_BUCKETS, Gauge, Registry
from repro.obs.trace import NULL, Tracer, _NULL_SPAN, hlo_scopes


# ---------------------------------------------------------------- tracer


def test_span_nesting_emits_one_complete_event_per_span():
    tr = Tracer()
    with tr.span("outer", step=0):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    evs = [e for e in tr.events if e.get("ph") == "X"]
    assert [e["name"] for e in evs] == ["inner", "inner", "outer"]
    outer = evs[-1]
    assert outer["args"] == {"step": 0}
    # children are contained within the parent's interval
    for inner in evs[:2]:
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_disabled_tracer_is_a_noop():
    assert NULL.enabled is False
    assert NULL.span("x") is _NULL_SPAN
    with NULL.span("x", a=1):
        NULL.instant("ev", k=2)
        NULL.counter("c", v=3.0)
        NULL.begin("req", id=1)
        NULL.end("req", id=1)
    assert NULL.events == []


def test_chrome_export_is_perfetto_shaped(tmp_path):
    tr = Tracer()
    with tr.span("phase"):
        tr.instant("blip", detail="x")
    tr.counter("load", weight=3.0)
    tr.begin("request", id=7, weight=12)
    tr.end("request", id=7, outcome="ok")
    path = tr.save(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"X", "i", "C", "b", "e", "M"} <= phases
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] != "M":
            assert "ts" in e
    b = next(e for e in evs if e["ph"] == "b")
    en = next(e for e in evs if e["ph"] == "e")
    assert b["id"] == en["id"] == 7 and b["cat"] == en["cat"] == "async"


def test_tracer_assigns_one_lane_per_thread():
    tr = Tracer()

    barrier = threading.Barrier(3)       # keep idents from being recycled

    def work():
        barrier.wait()
        with tr.span("t"):
            pass
        barrier.wait()

    threads = [threading.Thread(target=work) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with tr.span("main"):
        pass
    tids = {e["tid"] for e in tr.events if e["ph"] == "X"}
    assert len(tids) == 4
    meta = [e for e in tr.events if e["ph"] == "M"]
    assert {e["tid"] for e in meta} == {e["tid"] for e in tr.events
                                        if e["ph"] == "X"}


def test_span_summary_aggregates_by_name():
    tr = Tracer()
    for _ in range(3):
        with tr.span("step"):
            pass
    s = tr.span_summary()["step"]
    assert s["count"] == 3
    assert s["total_us"] >= s["max_us"] >= 0
    assert s["mean_us"] == pytest.approx(s["total_us"] / 3)


# ---------------------------------------------------------------- metrics


def test_counter_labels_are_independent_series():
    reg = Registry()
    c = reg.counter("farm_events_total", "events")
    c.inc(event="retry")
    c.inc(event="retry")
    c.inc(event="quarantine")
    assert c.value(event="retry") == 2
    assert c.value(event="quarantine") == 1
    assert c.value(event="nope") == 0
    snap = reg.snapshot()["farm_events_total"]
    assert snap["kind"] == "counter"
    got = {tuple(s["labels"].items()): s["value"] for s in snap["series"]}
    assert got == {(("event", "retry"),): 2.0,
                   (("event", "quarantine"),): 1.0}


def test_counter_rejects_negative_increment():
    with pytest.raises(ValueError):
        Registry().counter("c").inc(-1)


def test_registry_is_idempotent_and_kind_checked():
    reg = Registry()
    a = reg.counter("m", "first")
    b = reg.counter("m", "second help ignored")
    assert a is b and a.help == "first"
    with pytest.raises(TypeError):
        reg.gauge("m")
    with pytest.raises(TypeError):
        reg.histogram("m")


def test_gauge_set_and_inc():
    g = Registry().gauge("load")
    g.set(5.0, worker=0)
    g.inc(2.5, worker=0)
    g.set(1.0, worker=1)
    assert g.value(worker=0) == 7.5
    assert g.value(worker=1) == 1.0


def test_histogram_buckets_and_quantiles():
    reg = Registry()
    h = reg.histogram("lat", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0, 5000.0):
        h.observe(v)
    snap = reg.snapshot()["lat"]["series"][0]
    assert snap["counts"] == [2, 1, 1, 1]        # last = +inf overflow
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(5056.2)
    assert h.quantile(0.5) == 10.0       # 3rd of 5 obs lands in (1, 10]
    assert h.quantile(0.9) == float("inf")
    assert np.isnan(h.quantile(0.5, other="series"))


def test_default_buckets_sorted():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)


def test_registry_reset():
    reg = Registry()
    reg.counter("x").inc()
    reg.reset()
    assert reg.snapshot() == {}
    assert reg.get("x") is None


# ----------------------------------------------------------------- report


def test_report_renders_empty_and_full():
    assert "no observability data" in report.render()
    tr = Tracer()
    reg = Registry()
    with tr.span("superstep"):
        pass
    tr.counter("w0.queued_weight", weight=2.0)
    reg.counter("farm_events_total", "e").inc(event="retry")
    reg.histogram("engine_queue_wait_ticks", "w").observe(3.0)
    txt = report.render(tracer=tr, metrics=reg,
                        farm_stats={"n_workers": 2, "tasks": 5, "retries": 1,
                                    "worker_busy_s": [0.5, 0.25],
                                    "worker_tasks": [3, 2],
                                    "emitter_busy_s": 0.1})
    for needle in ("superstep", "w0.queued_weight", "farm_events_total",
                   "engine_queue_wait_ticks", "p50"):
        assert needle in txt


# ------------------------------------------------- instrumented runtimes


def test_traced_frontier_build_matches_untraced():
    ds = make_tree_dataset(np.random.default_rng(11), n=240)
    cfg = GrowConfig(max_depth=5)
    plain = frontier.build(ds, cfg)
    tr = Tracer()
    reg = Registry()
    with tr.span("grow"):
        traced, stats = frontier.build(ds, cfg, collect_stats=True,
                                       metrics=reg)
    assert trees_equal(plain, traced)

    summ = tr.span_summary()
    assert summ["grow"]["count"] == 1
    n_steps = len(stats)
    assert reg.gauge("frontier_supersteps").value() == n_steps
    assert reg.gauge("frontier_open_nodes").value() == traced.size
    assert reg.gauge("frontier_cases").value() == ds.n_cases
    assert reg.get("frontier_phase_seconds") is None


def _scored_pairs(tree, ds, cfg, mask) -> int:
    """The (node, attribute) pairs C4.5 scores in ``tree``, counted on the
    host: every node that C4.5 does not stop first (pure, small, deep),
    times the attributes still active there (all the mask allows but the
    discrete ones split on above it)."""
    t = tree.to_numpy()
    n = tree.size
    used = np.zeros((n, ds.n_attrs), bool)
    used[0] = ~mask
    for i in range(n):                        # children follow parents
        if t.node_nchild[i]:
            kids = slice(t.node_child0[i], t.node_child0[i] + t.node_nchild[i])
            used[kids] = used[i]
            if not ds.attr_is_cont[t.node_attr[i]]:
                used[kids, t.node_attr[i]] = True
    freq = t.node_freq[:n]
    pre_leaf = ((np.sum(freq > frontier.EPS_W, -1) <= 1)
                | (freq.sum(-1) < 2.0 * cfg.min_objs)
                | (t.node_depth[:n] >= cfg.max_depth))
    return int(np.sum(~used[~pre_leaf]))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_fused_build_counters_equal_the_stepwise_rows(impl):
    mixed = make_tree_dataset(np.random.default_rng(4), n=1500)
    # all discrete, multiway splits of up to 11 children, 5 classes
    census = datasets.load("us_census", scale=0.0004)
    for ds in (mixed, census):
        _check_counters(ds, impl, None)
    # attributes left out of the search are never scored
    _check_counters(census, impl, np.arange(census.n_attrs) % 3 > 0)


def _check_counters(ds, impl, attr_mask):
    cfg = GrowConfig(max_nodes=2048, frontier_slots=8, compact_min_bucket=128)
    reg = Registry()
    fused = frontier.build(ds, cfg, impl=impl, metrics=reg,
                           attr_mask=attr_mask)
    stepwise, rows = frontier.build(ds, cfg, impl=impl, collect_stats=True,
                                    metrics=Registry(), attr_mask=attr_mask)
    mask = np.ones(ds.n_attrs, bool) if attr_mask is None else attr_mask
    assert trees_equal(fused, stepwise)
    got = {name: reg.gauge(name).value() for name in (
        "frontier_supersteps", "frontier_open_nodes",
        "frontier_live_case_steps", "frontier_hist_case_steps",
        "frontier_tested_pairs", "frontier_cases", "frontier_slots",
        "frontier_attrs")}
    assert got == {
        "frontier_supersteps": len(rows),
        "frontier_open_nodes": sum(r["n_processed"] for r in rows),
        "frontier_live_case_steps": sum(r["n_active"] for r in rows),
        "frontier_hist_case_steps": sum(r["n_hist"] for r in rows),
        "frontier_tested_pairs": sum(r["n_tested"] for r in rows),
        "frontier_cases": ds.n_cases,
        "frontier_slots": cfg.frontier_slots,
        "frontier_attrs": ds.n_attrs}
    assert rows[0]["n_active"] == ds.n_cases          # the root holds all
    if impl == "jnp":                                 # nothing is gathered
        assert all(r["n_hist"] == ds.n_cases for r in rows)
    else:                                             # a bucket holds them
        assert all(r["n_active"] <= r["n_hist"] <= ds.n_cases for r in rows)
        assert any(r["n_hist"] < ds.n_cases for r in rows)
    # the whole K x A grid is computed every superstep; C4.5 scores a part
    assert rows[0]["n_tested"] == mask.sum()          # the root, every attr
    assert all(r["n_tested"] <= cfg.frontier_slots * ds.n_attrs for r in rows)
    assert 0 < got["frontier_tested_pairs"] < (
        len(rows) * cfg.frontier_slots * ds.n_attrs)
    assert got["frontier_tested_pairs"] == _scored_pairs(fused, ds, cfg, mask)


def test_wide_totals_pass_2_pow_31_exactly():
    words = jnp.zeros((2,), jnp.int32)
    steps = [2**31 - 1, 2**31 - 1, 2**30, 12345, 2**30 - 1, 0]
    for v in steps:
        words = frontier._wide_add(words, jnp.int32(v))
    assert float(frontier._WideTotal(words)) == sum(steps)
    assert int(words[1]) < 2**frontier.LOW_BITS


def test_build_leaves_device_values_in_the_registry(monkeypatch):
    seen = {}
    set_value = Gauge.set

    def spy(self, value, **labels):
        seen[self.name] = value
        set_value(self, value, **labels)

    monkeypatch.setattr(Gauge, "set", spy)
    ds = make_tree_dataset(np.random.default_rng(6), n=300)
    reg = Registry()
    tree = frontier.build(ds, GrowConfig(max_depth=4), metrics=reg)
    assert isinstance(seen["frontier_supersteps"], jax.Array)
    assert isinstance(seen["frontier_open_nodes"], jax.Array)
    for name in ("frontier_live_case_steps", "frontier_hist_case_steps"):
        assert isinstance(seen[name].words, jax.Array)
    assert reg.gauge("frontier_open_nodes").value() == tree.size
    snap = reg.snapshot()
    assert all(isinstance(m["series"][0]["value"], float)
               for m in snap.values())


# The loop's scaffolding, which owns no work of a phase.
SCAFFOLDING = {"while", "conditional", "tuple", "parameter",
               "get-tuple-element", "copy"}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")


def _instructions_that_run(hlo_text):
    """(name, opcode) of every instruction in a computation the program
    runs as such: the entry, loop conditions and bodies, branches and
    called computations, not what a fusion or a reduction applies."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            entry = m.group(1) if line.startswith("ENTRY") else entry
        elif cur is not None and (mi := _INSTRUCTION.match(line)):
            cur.append((mi.group(1), mi.group(2), line))
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for _, opcode, line in comps[comp]:
            todo += re.findall(r"(?:condition|body)=%([^\s,)]+)", line)
            for branches in re.findall(r"branch_computations=\{([^}]*)\}",
                                       line):
                todo += [b.strip().lstrip("%") for b in branches.split(",")]
            if opcode == "call":
                todo += re.findall(r"to_apply=%([^\s,)]+)", line)
    return [(n, op) for c in seen for n, op, _ in comps[c]]


def _small_build_program(impl, n_cases):
    """The compiled text of a small build and its ``build_scopes()``."""
    ds = make_tree_dataset(np.random.default_rng(9), n=n_cases)
    cfg = GrowConfig(max_nodes=1024, frontier_slots=8, compact_min_bucket=128)
    frontier._DISPATCHED.clear()
    frontier.build(ds, cfg, impl=impl)
    ((prob, _, specs),) = frontier._DISPATCHED
    text = frontier._build_jit.lower(
        *[jax.ShapeDtypeStruct(s, d) for s, d in specs], prob=prob,
        impl=impl).compile().as_text()
    return text, frontier.build_scopes()


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_every_build_instruction_has_a_frontier_scope(impl):
    text, scopes = _small_build_program(impl, 1200)
    ops = _instructions_that_run(text)
    assert len(ops) > 50
    missing = [(n, op) for n, op in ops
               if n not in scopes and op not in SCAFFOLDING]
    assert missing == []
    phases = {s.scope for s in scopes.values()}
    assert {s.rule for s in scopes.values()} <= {1, 2, 3, 4}
    assert {"frontier.init", "frontier.split_pre", "frontier.select",
            "frontier.split_att", "frontier.compact", "frontier.split_post",
            "frontier.route"} <= phases
    assert phases <= {"frontier.init", "frontier.split_pre",
                      "frontier.select", "frontier.split_att",
                      "frontier.compact", "frontier.split_post",
                      "frontier.route"}


_GATHER = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[(\d+)[\],].*? gather\(",
                     re.MULTILINE)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_route_gathers_nothing_over_the_cases(impl):
    # Routing looks each case's per-slot record up by a one-hot matmul
    # over the K slots, and splitPre gives each case its slot by
    # subtraction: no gather with a result over the N cases may carry a
    # frontier scope, the route's least of all.  A plain x[idx] over N
    # indices shows that the search finds such gathers.
    n = 1200
    witness = jax.jit(lambda x, idx: x[idx]).lower(
        jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32)).compile().as_text()
    assert [d for _, d in _GATHER.findall(witness) if int(d) == n]
    text, scopes = _small_build_program(impl, n)
    over_cases = [scopes[name].scope if name in scopes else None
                  for name, dim in _GATHER.findall(text) if int(dim) == n]
    assert "frontier.route" not in over_cases
    assert [s for s in over_cases if s and s.startswith("frontier.")] == []


def test_hlo_scopes_reads_own_fused_and_neighbouring_scopes():
    text = """HloModule m

%fused (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %n = s32[4]{0} negate(%p), metadata={op_name="jit(f)/while/body/f.b/f.c/neg"}
}

%body (t: (s32[4])) -> (s32[4]) {
  %t = (s32[4]{0}) parameter(0)
  %g = s32[4]{0} get-tuple-element(%t), index=0
  %made = s32[4]{0} copy(%g)
  %fusion.1 = s32[4]{0} fusion(%made), kind=kLoop, calls=%fused
  %own = s32[4]{0} add(%fusion.1, %fusion.1), metadata={op_name="jit(f)/while/body/f.a/add"}
  %spare = s32[4]{0} copy(%g)
  ROOT %r = (s32[4]{0}) tuple(%own)
}

%cond (t: (s32[4])) -> pred[] {
  %t.1 = (s32[4]{0}) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main (x: s32[4]) -> (s32[4]) {
  %x = s32[4]{0} parameter(0), metadata={op_name="x"}
  %fill = s32[4]{0} broadcast(%x), dimensions={}
  %init = (s32[4]{0}) tuple(%fill)
  ROOT %w = (s32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
}
"""
    got = hlo_scopes(text, "f.", entry_scope="f.init")
    assert got["n"] == ("f.c", 1)              # innermost of its own
    assert got["fusion.1"] == ("f.c", 2)       # from what it fuses
    assert got["own"] == ("f.a", 1)
    assert got["made"] == ("f.c", 3)           # from the fusion that uses it
    assert "spare" not in got                  # no neighbour has a scope
    assert got["fill"] == ("f.init", 4)        # the loop's initial state
    for scaffolding in ("w", "init", "t", "g", "r", "x"):
        assert scaffolding not in got
    assert "fill" not in hlo_scopes(text, "f.")   # no entry scope given


def test_enabled_span_lands_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs.test_span"):
            (jnp.arange(8) * 2).block_until_ready()
        with NULL.span("obs.null_span"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "obs.test_span" in names
    assert "obs.null_span" not in names
    assert tr.span_summary()["obs.test_span"]["count"] == 1


def test_build_annotations_land_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    ds = make_tree_dataset(np.random.default_rng(8), n=200)
    cfg = GrowConfig(max_depth=3)
    frontier.build(ds, cfg)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        frontier.build(ds, cfg)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"frontier.build", "frontier.to_device"} <= names


def test_traced_farm_chaos_build_matches_oracle(tmp_path):
    ds = make_tree_dataset(np.random.default_rng(5), n=220)
    cfg = GrowConfig(max_depth=6)
    oracle = farm_build.build(ds, cfg, n_workers=1)
    tr = Tracer()
    reg = Registry()
    inj = FaultInjector(seed=3, spec=FaultSpec(crash_p=0.25))
    stats = {}
    tree = farm_build.build(ds, cfg, n_workers=4, injector=inj,
                            fault=FaultPolicy(max_retries=8, backoff_base=0),
                            stats_out=stats, tracer=tr, metrics=reg)
    assert trees_equal(oracle, tree)
    assert stats["retries"] > 0

    names = {e["name"] for e in tr.events}
    assert {"task", "emitter", "task.dispatch", "task.retry"} <= names
    snap = reg.snapshot()
    events = {s["labels"]["event"]: s["value"]
              for s in snap["farm_events_total"]["series"]}
    assert events.get("retries") == stats["retries"]
    assert snap["farm_tasks_done_total"]["series"][0]["value"] == \
        sum(stats["worker_tasks"])
    # trace survives a JSON round-trip (Perfetto-loadable)
    path = tr.save(str(tmp_path / "farm.json"))
    assert json.loads(open(path).read())["traceEvents"]


def test_tracing_disabled_leaves_no_residue():
    ds = make_tree_dataset(np.random.default_rng(2), n=200)
    cfg = GrowConfig(max_depth=4)
    n0 = len(NULL.events)
    a = frontier.build(ds, cfg)
    with NULL.span("grow"):
        b = frontier.build(ds, cfg)
    assert trees_equal(a, b)
    assert len(NULL.events) == n0
