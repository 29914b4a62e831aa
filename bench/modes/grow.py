"""Grow mode: whole trees back to back through ``frontier.build``.

Set-up makes the configuration's training set (its cases in the order the
seed draws), and runs one build on the same shapes with every case in one
class, which loads or compiles the very program the window runs while its
root stops at once.  The window then starts whole builds back to back, each
ending in a host read of the tree, and closes when the first build that
finishes at or after ``--seconds`` finishes: ``tree_s`` is that time over
the number of builds.

After the window the first tree is checked node by node against the float64
C4.5 reference (:mod:`bench.c45_ref`), and every later tree must equal it.
In a control run (``bench/control.py``) the split that the reference takes
when it scores in bfloat16 stands in for the program's at every node, and
its gain gap is the one compared.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import c45_ref, data, work

TREE_FIELDS = ("node_attr", "node_split_bin", "node_child0", "node_nchild",
               "node_class", "node_freq", "node_depth")


def _host_tree(tree) -> dict:
    n = int(tree.n_nodes)
    out = {f: np.asarray(getattr(tree, f))[:n] for f in TREE_FIELDS}
    out["n_nodes"] = n
    return out


def _same(a: dict, b: dict) -> bool:
    return a["n_nodes"] == b["n_nodes"] and all(
        np.array_equal(a[f], b[f]) for f in TREE_FIELDS)


def run(run) -> dict:
    from repro.core import frontier
    from repro.core.binning import BinnedDataset
    from repro.core.config import GrowConfig

    grow = run.config["grow"]
    with run.span("data"):
        ds = data.permuted(data.make(run.config["data"]), run.seed)
    cfg = GrowConfig(**grow["config"])
    impl = grow["impl"]
    bds = BinnedDataset(
        x=ds["x"], y=ds["y"], w=np.ones(len(ds["y"]), np.float32),
        attr_is_cont=ds["attr_is_cont"], n_bins=ds["n_bins"],
        bin_edges=tuple(np.arange(b, dtype=np.float64)
                        for b in ds["n_bins"]),
        n_classes=ds["n_classes"])
    with run.span("warmup"):
        warm = dataclasses.replace(bds, y=np.zeros_like(ds["y"]))
        int(frontier.build(warm, cfg, impl=impl).n_nodes)

    trees = []
    with run.window():
        t0 = time.perf_counter()
        while True:
            with run.span("build"):
                tree = frontier.build(bds, cfg, impl=impl)
            with run.span("readback"):
                trees.append(_host_tree(tree))
            elapsed = time.perf_counter() - t0
            if elapsed >= run.seconds:
                break
    run.read_memory()
    del tree, bds

    # ---- correctness -------------------------------------------------------
    first = trees[0]
    limits = run.config["limits"]
    ref = dict(min_objs=cfg.min_objs, max_depth=cfg.max_depth,
               eps_gain=grow["eps_gain"])
    ctl_dtype = None
    if run.control:
        import ml_dtypes
        ctl_dtype = ml_dtypes.bfloat16
    with run.span("reference"):
        readings = c45_ref.check_tree(ds, first, ref, control_dtype=ctl_dtype)
    gap = readings["gain_gap_bits"]
    if run.control:
        run.program_readings["gain_gap_bits"] = gap
        gap = readings["control_gain_gap_bits"]
    differ = sum(not _same(first, t) for t in trees[1:])
    h = max([2] + [int(b) for b, c in zip(ds["n_bins"], ds["attr_is_cont"])
                   if not c])
    forced_leaf_at = cfg.max_nodes - cfg.frontier_slots * h
    run.notes.append(
        f"{len(trees)} builds, {first['n_nodes']} nodes, depth "
        f"{int(first['node_depth'].max())}; widest gap at node "
        f"{readings['gain_gap_node']}; faults {readings['fault_kinds']}")
    run.check("freq_mismatch_nodes", readings["freq_mismatch_nodes"], 0)
    run.check("structure_faults", readings["structure_faults"], 0)
    run.check("gain_gap_bits", gap, limits["gain_gap_bits"])
    run.check("trees_unlike_first", differ, 0)
    run.check("nodes_past_forced_leaf_threshold",
              max(0, first["n_nodes"] - forced_leaf_at), 0)

    per_tree = {
        "histogram": work.histogram(
            first["node_freq"], first["node_depth"],
            n_attrs=ds["x"].shape[1], min_objs=ref["min_objs"],
            max_depth=ref["max_depth"]),
        "split_gain": work.split_gain(
            first["node_freq"], first["node_depth"],
            n_attrs=ds["x"].shape[1], n_bins=int(ds["n_bins"].max()),
            n_classes=ds["n_classes"], min_objs=ref["min_objs"],
            max_depth=ref["max_depth"]),
    }
    run.layer_inputs.update(
        units=len(trees),
        work={k: (ops * len(trees), nb * len(trees))
              for k, (ops, nb) in per_tree.items()})
    wrong = len(trees) if not run.correct else 0
    return {"tree_s": elapsed / len(trees), "attempted": len(trees),
            "failed": wrong}

