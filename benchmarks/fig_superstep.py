"""Per-superstep splitAtt timing: jnp vs pallas vs pallas+compaction.

Replays one frontier build's superstep trajectory (driven by the jnp
reference engine so every variant sees the *same* states) and times each
splitAtt implementation at every step, recording the live-case count.  The
point of the figure: with active-case compaction the pallas superstep cost
tracks ``n_active`` (the open frontier's live cases) while the all-N path
stays flat at O(N) — the deep-tree half of the build stops paying full-HBM
traffic to count a handful of rows.

Emits the usual CSV rows *and* writes a ``BENCH_superstep.json`` trajectory
artifact (path overridable via ``BENCH_OUT``) so later PRs can diff perf
against this baseline — ``benchmarks/check_regression.py`` is the gate.

Knobs for CI smoke runs (all env vars):

  * ``BENCH_SCALE``     — global dataset scale multiplier (common.py);
  * ``BENCH_MAX_STEPS`` — cap on replayed supersteps (default 48);
  * ``BENCH_VARIANTS``  — comma list of variants to time; ``jnp`` always
    runs (it drives the shared state trajectory);
  * ``TRACE_OUT``       — if set, saves a Perfetto-loadable trace of the
    replay (one span per timed variant call, ``n_active`` counter track).

The artifact also embeds a ``metrics`` snapshot (per-variant superstep
histograms from :mod:`repro.obs.metrics`).

Off-TPU the kernels run in interpret mode, so absolute pallas-vs-jnp times
are meaningless there (the JSON records the backend); the compaction-vs-full
ratio on deep supersteps is meaningful everywhere — both sides run the same
kernel, only the case-tile grid differs.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

if __package__ in (None, ""):      # `python benchmarks/fig_superstep.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import common
from repro.core import frontier
from repro.core.config import GrowConfig
from repro.core.frontier import FrontierProblem
from repro.data import datasets
from repro.kernels import compaction
from repro.obs.metrics import Registry
from repro.obs.trace import Tracer

DATASET = "syd10m9a"          # QUEST stand-in: 9 attrs, deep tree (Table 1)
MAX_BINS = 32                 # keeps interpret-mode grids CPU-viable
MAX_STEPS = int(os.environ.get("BENCH_MAX_STEPS", "48"))
MIN_BUCKET = 256


def _variants(ds):
    base = dict(max_nodes=1 << 14, frontier_slots=64,
                compact_min_bucket=MIN_BUCKET)
    all_v = {
        "jnp": (GrowConfig(**base), "jnp"),
        "pallas": (GrowConfig(**base, compact=False), "pallas"),
        "pallas_compact": (GrowConfig(**base, compact=True), "pallas"),
    }
    want = os.environ.get("BENCH_VARIANTS")
    if not want:
        return all_v
    keep = {v.strip() for v in want.split(",")} | {"jnp"}   # jnp drives
    unknown = keep - set(all_v)
    if unknown:
        raise SystemExit(f"BENCH_VARIANTS: unknown {sorted(unknown)} "
                         f"(have {sorted(all_v)})")
    return {k: v for k, v in all_v.items() if k in keep}


def run() -> list[dict]:
    ds = datasets.load(DATASET, scale=common.SCALES[DATASET], seed=0,
                       max_bins=MAX_BINS)
    x = jnp.asarray(ds.x)
    y = jnp.asarray(ds.y)
    w = jnp.asarray(ds.w, jnp.float32)
    cont = jnp.asarray(ds.attr_is_cont)
    nb = jnp.asarray(ds.n_bins, jnp.int32)

    variants = _variants(ds)
    steps_fns = {}
    for vname, (cfg, impl) in variants.items():
        prob = FrontierProblem.from_dataset(ds, cfg)
        steps_fns[vname] = jax.jit(frontier._superstep_fn(prob, impl))

    drive_cfg, _ = variants["jnp"]
    drive_prob = FrontierProblem.from_dataset(ds, drive_cfg)
    state = frontier.init_state(drive_prob, y, w)

    trace_out = os.environ.get("TRACE_OUT")
    tracer = Tracer(enabled=bool(trace_out))
    registry = Registry()
    m_step = registry.histogram(
        "bench_superstep_seconds", "timed superstep call, variant= label")

    steps: list[dict] = []
    i = 0
    while bool(state.open_nodes < state.n_nodes) and i < MAX_STEPS:
        row = {"step": i, "n_open": int(state.n_nodes - state.open_nodes)}
        for vname, fn in steps_fns.items():
            with tracer.span(f"superstep.{vname}", step=i):
                (_, stats), secs = common.timed(fn, state, x, y, w, cont, nb,
                                                repeats=3)
            row[f"t_{vname}_s"] = secs
            row["n_active"] = int(stats["n_active"])
            m_step.observe(secs, variant=vname)
        tracer.counter("n_active", value=row["n_active"])
        state, _ = steps_fns["jnp"](state, x, y, w, cont, nb)
        steps.append(row)
        i += 1

    n = ds.n_cases
    deep = [s for s in steps if s["n_active"] <= n // 4]
    full = [s for s in steps if s["n_active"] > n // 4]
    artifact = {
        "dataset": DATASET,
        "scale": common.SCALES[DATASET],
        "n_cases": n,
        "n_attrs": ds.n_attrs,
        "max_bins": MAX_BINS,
        "backend": jax.default_backend(),
        "frontier_slots": 64,
        "compact_min_bucket": MIN_BUCKET,
        "buckets": list(compaction.bucket_sizes(n, min_bucket=MIN_BUCKET)),
        "steps": steps,
        "metrics": registry.snapshot(),
    }
    out_path = os.environ.get("BENCH_OUT", "BENCH_superstep.json")
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    if trace_out:
        tracer.save(trace_out)

    def mean(rows, key):
        return float(np.mean([r[key] for r in rows])) if rows else float("nan")

    rows = []
    for vname in variants:
        rows.append({
            "name": f"superstep/{vname}",
            "us_per_call": f"{mean(steps, f't_{vname}_s') * 1e6:.1f}",
            "n_steps": len(steps),
            "dataset": DATASET,
            "n_cases": n,
        })
    if {"pallas", "pallas_compact"} <= set(variants):
        deep_full = mean(deep, "t_pallas_s")
        deep_compact = mean(deep, "t_pallas_compact_s")
        rows.append({
            "name": "superstep/deep_compaction_speedup",
            "us_per_call": "",
            "n_deep_steps": len(deep),
            "n_shallow_steps": len(full),
            "mean_active_deep": int(mean(deep, "n_active")) if deep else 0,
            "t_deep_full_us": f"{deep_full * 1e6:.1f}",
            "t_deep_compact_us": f"{deep_compact * 1e6:.1f}",
            "speedup": f"{deep_full / deep_compact:.2f}" if deep else "nan",
            "artifact": out_path,
        })
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    common.emit(run())
