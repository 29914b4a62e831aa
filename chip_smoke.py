"""Chip smoke test: the Pallas tree build and forest serving on one TPU.

    python chip_smoke.py [--seed 0] [--out DIR]

Runs the system's main path once, in this one process, through the entry
points a user calls, on data generated from ``--seed``:

  oracle   SyD10M9A (QUEST function 5) at 50,000 cases with the paper's
           grow config (2**18 nodes, 256 slots);
           ``c45.build``, ``frontier.build(impl="jnp")`` and
           ``frontier.build(impl="pallas")`` must give equal trees.
  compact  the stream-compaction kernel's live-case ids against
           ``jnp.nonzero`` at both benchmark cells' case counts, for a
           sparse and a clustered live set (the Mosaic lowering, which
           interpret mode cannot check).
  scale    the same schema and config at ``SCALE_CASES`` cases.  First
           both splitAtt kernels are checked against the jnp path on a full
           256-slot frontier (equal counts; split-gain scores and bins equal
           bit for bit).  Then, with active-case compaction on, the pallas
           tree must equal the jnp tree and stay clear of the node capacity
           (no forced leaves).
  serving  an 8-tree forest grown with ``train_forest(impl="frontier",
           kernel_impl="pallas")`` on the oracle data, published to a
           registry under ``--out``, and 1,000 single-row requests served
           through ``BatchPredictService`` over a pallas ``InferReplica``;
           every answer must equal ``Forest.predict(impl="ref")``.

It also checks that the build and the serving predict lower to Mosaic
kernels (``tpu_custom_call``) and not to interpret mode.  It exits non-zero,
printing no result, when the backend is not a TPU, when it is run outside a
checkout of this repository, or when any check fails.  Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Seconds printed per phase include compilation; they are set-up information,
not a speed measurement.
"""

from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ORACLE_CASES = 50_000
# SyD10M9A's published size is 10,000,000 cases.  The scale phase is cut to
# the largest count measured on a v5e whose seed-0 tree stays below the
# forced-leaf threshold of the 2**18-node capacity (198,160 of 257,024 nodes)
# and whose two builds fit the smoke's time (about 250 s each with compile).
# Counts from 4.5M to 10M were not measured.
SCALE_CASES = 4_000_000
FOREST_TREES = 8
REQUESTS = 1_000
UNKNOWN_FRAC = 0.05
# The case counts of the benchmark's grow cells (syd10m9a, us_census).
COMPACT_CASES = (500_000, 245_828)


def _use_checkout_src() -> None:
    src = ROOT / "src"
    if not (src / "repro" / "core" / "frontier.py").is_file():
        sys.exit(f"chip_smoke: no repro package under {src}; run this "
                 "script from a checkout of the repository")
    sys.path.insert(0, str(src))


def _kernels_in(lowered_text: str, names) -> dict[str, bool]:
    calls = [line for line in lowered_text.splitlines()
             if "tpu_custom_call" in line]
    return {n: any(f'kernel_name = "{n}"' in line for line in calls)
            for n in names}


def _first_difference(a, b) -> str:
    """Where two trees first differ, for the log of a failed check."""
    a, b = a.to_numpy(), b.to_numpy()
    n = min(int(a.n_nodes), int(b.n_nodes))
    fields = ("node_attr", "node_split_bin", "node_nchild", "node_class")
    for i in range(n):
        got = [int(getattr(a, f)[i]) for f in fields]
        want = [int(getattr(b, f)[i]) for f in fields]
        if got != want:
            return (f"node {i} (depth {int(a.node_depth[i])}): "
                    f"attr/split/nchild/class {got} vs {want}")
    return f"sizes {int(a.n_nodes)} vs {int(b.n_nodes)}"


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"))
    args = ap.parse_args()

    _use_checkout_src()
    from repro import compile_cache
    cache = compile_cache.enable()

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: backend is {jax.default_backend()!r}, not 'tpu';"
              " refusing to run the kernels in interpret mode",
              file=sys.stderr)
        return 2

    import jax.numpy as jnp
    import numpy as np

    from repro.core import c45, frontier
    from repro.core.config import GrowConfig
    from repro.core.tree import trees_equal
    from repro.data import quest
    from repro.ensemble import ForestConfig, publish_forest, train_forest
    from repro.infer import forest as forest_mod
    from repro.infer import registry
    from repro.infer.service import (BatchPredictService, InferReplica,
                                     PredictRequest)

    dev = jax.devices()[0]
    print(f"device_kind: {dev.device_kind}")
    print(f"devices: {len(jax.devices())}  compile cache: {cache}")
    cfg = GrowConfig(max_nodes=1 << 18, frontier_slots=256)
    checks = Checks()

    def setup_seconds(phase: str, t0: float) -> None:
        print(f"  {phase} wall seconds incl. compile (set-up information, "
              f"not speed): {time.perf_counter() - t0:.1f}", flush=True)

    def build_lowering(ds, impl: str) -> str:
        """Lowered text of the whole-build jit, with build()'s arguments."""
        prob = frontier.FrontierProblem.from_dataset(ds, cfg)
        return frontier._build_jit.lower(
            jnp.asarray(ds.x), jnp.asarray(ds.y),
            jnp.asarray(ds.w, jnp.float32), jnp.ones((ds.n_attrs,), bool),
            jnp.asarray(ds.attr_is_cont), jnp.asarray(ds.n_bins, jnp.int32),
            prob=prob, impl=impl).as_text()

    def kernel_parity(ds) -> None:
        """Both splitAtt kernels against the jnp path on one full frontier
        (every case in one of the slots): equal counts, and split-gain
        scores and bins equal bit for bit under both criteria."""
        from repro.core import entropy
        from repro.kernels import ops
        prob = frontier.FrontierProblem.from_dataset(ds, cfg)
        k = cfg.frontier_slots
        args = (jnp.asarray(ds.x), jnp.asarray(ds.y),
                jnp.asarray(ds.w, jnp.float32),
                jnp.arange(ds.n_cases, dtype=jnp.int32) % k)
        shape = dict(n_slots=k, n_bins=prob.n_bins_max,
                     n_classes=prob.n_classes)
        hist = ops.frontier_histogram(*args, **shape)
        want = frontier.frontier_histogram_jnp(*args, **shape)
        checks.expect(bool(jnp.array_equal(hist, want)),
                      f"histogram kernel equals jnp over {k} slots")
        tw = jnp.sum(hist[:, 0], axis=(1, 2))
        cont = jnp.asarray(ds.attr_is_cont)
        n_bins = jnp.asarray(ds.n_bins, jnp.int32)
        for crit in ("gain", "gain_ratio"):
            s_k, b_k = ops.split_gain(hist, tw, cont, n_bins,
                                      min_objs=cfg.min_objs, criterion=crit)
            s_r, b_r = jax.jit(functools.partial(
                entropy.gains_from_histogram, min_objs=cfg.min_objs,
                criterion=crit))(hist, total_w=tw, attr_is_cont=cont,
                                 n_bins=n_bins)
            differ = int(jnp.sum(s_k != s_r))
            checks.expect(differ == 0 and bool(jnp.array_equal(b_k, b_r)),
                          f"split_gain ({crit}) equals entropy bit for bit "
                          f"({differ} of {s_k.size} scores differ)")

    def compaction_parity(seed: int) -> None:
        """live_case_ids equals nonzero at the largest gather bucket, for a
        sparse live set (3% of cases, scattered) and a clustered one (one
        run of 20,000 cases and a tail of 3,000)."""
        from repro.kernels import compaction, ops
        rng = np.random.default_rng(seed)
        for n in COMPACT_CASES:
            size = compaction.bucket_sizes(
                n, min_bucket=cfg.compact_min_bucket)[-2]
            clustered = np.zeros(n, bool)
            clustered[n // 3:n // 3 + 20_000] = True
            clustered[-3_000:] = True
            for name, live in (("sparse", rng.random(n) < 0.03),
                               ("clustered", clustered)):
                slot = jnp.asarray(np.where(live, rng.integers(0, 256, n),
                                            -1).astype(np.int32))
                want = jnp.nonzero(slot >= 0, size=size, fill_value=0)[0]
                got = ops.live_case_ids(slot, size=size)
                checks.expect(bool(jnp.array_equal(got, want)),
                              f"live_case_ids equals nonzero at {n} cases, "
                              f"{name} ({int(live.sum())} live, {size} ids)")

    def no_forced_leaves(ds, tree) -> bool:
        # A superstep adds at most slots * widest-split children, so a tree
        # that ends this far below capacity never hit the overflow guard.
        h = frontier.FrontierProblem.from_dataset(ds, cfg).max_children
        return tree.size <= cfg.max_nodes - cfg.frontier_slots * h

    def build(label: str, fn, *a, **kw):
        t = time.perf_counter()
        tree = fn(*a, **kw)
        n = tree.size                                # waits for the device
        print(f"  {label}: {n} nodes, {time.perf_counter() - t:.1f} s incl. "
              "compile (set-up information, not speed)", flush=True)
        return tree

    # ---- oracle phase -------------------------------------------------------
    t0 = time.perf_counter()
    ds = quest.syd(ORACLE_CASES, seed=args.seed)
    t_pal = build("pallas build", frontier.build, ds, cfg, impl="pallas")
    t_jnp = build("jnp build", frontier.build, ds, cfg, impl="jnp")
    t_c45 = build("c45 build", c45.build, ds, cfg, capacity=cfg.max_nodes)
    eq_jnp, eq_c45 = trees_equal(t_jnp, t_pal), trees_equal(t_c45, t_pal)
    print(f"oracle: cases={ds.n_cases} n_nodes={t_pal.size} "
          f"depth={t_pal.depth} c45==pallas={eq_c45} jnp==pallas={eq_jnp}")
    for ok, ref, name in ((eq_c45, t_c45, "c45"), (eq_jnp, t_jnp, "jnp")):
        if not ok:
            print(f"  pallas vs {name}: {_first_difference(t_pal, ref)}")
    checks.expect(eq_c45 and eq_jnp, "oracle trees equal (c45 = jnp = pallas)")
    checks.expect(no_forced_leaves(ds, t_pal), "oracle tree below capacity")
    root_w = float(np.asarray(t_pal.node_freq)[0].sum())
    checks.expect(root_w > 2 ** 8, f"histogram counts above 2^8 (root weight "
                  f"{root_w:.0f})")
    found = _kernels_in(build_lowering(ds, "pallas"),
                        ("frontier_histogram", "split_gain", "live_case_ids"))
    checks.expect(all(found.values()),
                  f"build lowers to tpu_custom_call kernels {found}")
    setup_seconds("oracle", t0)

    # ---- compaction phase ---------------------------------------------------
    t0 = time.perf_counter()
    compaction_parity(args.seed)
    setup_seconds("compact", t0)

    # ---- scale phase --------------------------------------------------------
    t0 = time.perf_counter()
    ds_big = quest.syd(SCALE_CASES, seed=args.seed)
    kernel_parity(ds_big)
    s_pal = build("pallas build", frontier.build, ds_big, cfg,
                  impl="pallas")
    s_jnp = build("jnp build", frontier.build, ds_big, cfg, impl="jnp")
    eq = trees_equal(s_jnp, s_pal)
    print(f"scale: cases={ds_big.n_cases} n_nodes={s_pal.size} "
          f"depth={s_pal.depth} capacity={cfg.max_nodes} jnp==pallas={eq}")
    if not eq:
        print(f"  pallas vs jnp: {_first_difference(s_pal, s_jnp)}")
    checks.expect(eq, "scale trees equal (jnp = pallas)")
    checks.expect(no_forced_leaves(ds_big, s_pal),
                  "scale tree below capacity")
    del ds_big, s_pal, s_jnp
    setup_seconds("scale", t0)

    # ---- serving phase ------------------------------------------------------
    t0 = time.perf_counter()
    out = Path(args.out)
    reg_root = out / "registry"
    shutil.rmtree(reg_root, ignore_errors=True)
    reg_root.mkdir(parents=True)
    fc = ForestConfig(n_trees=FOREST_TREES, seed=args.seed, grow=cfg)
    result = train_forest(ds, fc, impl="frontier", kernel_impl="pallas",
                          n_workers=1)
    path = publish_forest(str(reg_root), "syd_rf", result, ds)
    handle = registry.ModelHandle(str(reg_root), "syd_rf")
    forest = handle.stable
    print(f"serving: trees={forest.n_trees} capacity={forest.capacity} "
          f"levels={forest.n_levels} published={Path(path).name}")
    checks.expect(forest.capacity > 256, "traversal gathers node ids above "
                  f"256 (capacity {forest.capacity})")

    rng = np.random.default_rng(args.seed)
    rows = ds.x[rng.choice(ds.n_cases, REQUESTS, replace=False)].copy()
    rows[rng.random(rows.shape) < UNKNOWN_FRAC] = -1
    want = np.asarray(forest_mod.predict(forest, rows, ds.attr_is_cont,
                                         impl="ref"))
    svc = BatchPredictService(
        [InferReplica.from_handle(handle, ds.attr_is_cont, impl="pallas")],
        handle=handle, max_batch=128)
    for uid, row in enumerate(rows):
        svc.submit(PredictRequest(uid=uid, x_row=row))
    results = svc.run_until_drained()
    got = {r.uid: r.label for r in results}
    n_equal = sum(got.get(uid) == int(want[uid]) for uid in range(REQUESTS))
    print(f"serving: requests={REQUESTS} results={len(results)} "
          f"failures={len(svc.failed)} equal_to_ref={n_equal}/{REQUESTS}")
    checks.expect(n_equal == REQUESTS and not svc.failed,
                  "every request served and equal to Forest.predict(ref)")

    lowered = jax.jit(lambda x: forest_mod.predict(
        forest, x, ds.attr_is_cont, impl="pallas",
        max_depth=forest.n_levels)).lower(jnp.asarray(rows[:128])).as_text()
    found = _kernels_in(lowered, ("forest_predict",))
    checks.expect(all(found.values()),
                  f"serving predict lowers to tpu_custom_call {found}")
    setup_seconds("serving", t0)

    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
