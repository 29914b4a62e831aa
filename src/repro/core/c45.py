"""Sequential YaDT oracle — the reference semantics for every other engine.

A direct transliteration of the paper's Fig. 2/3/4 pseudo-code:

  tree::build       -> :func:`build` (breadth-first frontier queue, Fig. 4)
  node::splitPre    -> class frequencies + stop tests
  node::splitAtt(i) -> per-attribute gain via the shared histogram scorer
  node::splitPost   -> argmax, threshold, child creation

It operates on the EC4.5 rank-space representation (:mod:`repro.core.binning`)
and calls the *same* jnp scoring functions as the SPMD engine
(:mod:`repro.core.entropy`) on identical ``(A, B, C)`` histogram tensors, so
split decisions are bitwise comparable.  Being the semantic reference it also
implements full C4.5 unknown handling (fractional weights to all children)
behind ``GrowConfig.unknown_fractional``.

This engine is intentionally plain numpy + per-node Python — it is the
measurement baseline ("Seq.Time" of paper Table 2) and the source of per-task
costs for the farm simulator.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import numpy as np

from repro.core import entropy
from repro.core.binning import BinnedDataset
from repro.core.config import GrowConfig
from repro.core.tree import Tree

EPS_W = 1e-7

# One compiled scorer per histogram shape: the node loop calls it once per
# node, and op-by-op dispatch of the scorer's ordered sums would dominate.
_gains = jax.jit(entropy.gains_from_histogram,
                 static_argnames=("min_objs", "criterion"))


@dataclasses.dataclass
class _Task:
    """A node task on the farm stream (paper's ff_task, weight = r cases)."""
    node_id: int
    idx: np.ndarray        # case indices at the node
    w: np.ndarray          # case weights (may be fractional: unknowns)
    active: np.ndarray     # bool (A,) attributes still active
    depth: int


@dataclasses.dataclass
class _Nodes:
    """Append-only builder for the Tree arrays (ids in BFS order)."""
    attr: list
    split_bin: list
    child0: list
    nchild: list
    cls: list
    freq: list
    depth: list

    @staticmethod
    def new() -> "_Nodes":
        return _Nodes([], [], [], [], [], [], [])

    def add(self, *, cls: int, freq: np.ndarray, depth: int) -> int:
        i = len(self.attr)
        self.attr.append(-1)
        self.split_bin.append(-1)
        self.child0.append(0)
        self.nchild.append(0)
        self.cls.append(cls)
        self.freq.append(freq)
        self.depth.append(depth)
        return i

    def finish(self, n_classes: int, capacity: int | None = None) -> Tree:
        import jax.numpy as jnp
        n = len(self.attr)
        cap = capacity or n
        t = Tree.empty(cap, n_classes)
        t.node_attr = t.node_attr.at[:n].set(np.asarray(self.attr, np.int32))
        t.node_split_bin = t.node_split_bin.at[:n].set(
            np.asarray(self.split_bin, np.int32))
        t.node_child0 = t.node_child0.at[:n].set(
            np.asarray(self.child0, np.int32))
        t.node_nchild = t.node_nchild.at[:n].set(
            np.asarray(self.nchild, np.int32))
        t.node_class = t.node_class.at[:n].set(np.asarray(self.cls, np.int32))
        t.node_freq = t.node_freq.at[:n].set(
            np.stack(self.freq).astype(np.float32))
        t.node_depth = t.node_depth.at[:n].set(
            np.asarray(self.depth, np.int32))
        t.n_nodes = jnp.int32(n)
        return t


def node_histogram(ds: BinnedDataset, idx: np.ndarray, w: np.ndarray,
                   b_max: int | None = None) -> np.ndarray:
    """(A, B, C) weighted counts of known-valued cases at a node."""
    a_dim = ds.n_attrs
    b_dim = b_max or ds.max_bins
    c_dim = ds.n_classes
    hist = np.zeros((a_dim, b_dim, c_dim), np.float32)
    xb = ds.x[idx]                       # (r, A)
    y = ds.y[idx]
    for a in range(a_dim):
        b = xb[:, a]
        known = b >= 0
        if not known.any():
            continue
        flat = b[known].astype(np.int64) * c_dim + y[known]
        hist[a] += np.bincount(flat, weights=w[known],
                               minlength=b_dim * c_dim
                               ).reshape(b_dim, c_dim).astype(np.float32)
    return hist


def class_frequencies(ds: BinnedDataset, idx: np.ndarray, w: np.ndarray
                      ) -> np.ndarray:
    """computeFrequencies (paper §2.2): weighted class counts at the node."""
    return np.bincount(ds.y[idx], weights=w, minlength=ds.n_classes
                       ).astype(np.float32)


def split_pre(freq: np.ndarray, depth: int, cfg: GrowConfig) -> bool:
    """onlyOneClass() || fewCases() (paper §2.3) — True = make a leaf."""
    total = float(freq.sum())
    pure = int((freq > EPS_W).sum()) <= 1
    return pure or total < 2 * cfg.min_objs or depth >= cfg.max_depth


def split_att(hist: np.ndarray, total_w: float, ds: BinnedDataset,
              cfg: GrowConfig):
    """gainCalculation for every attribute at once (paper §2.6-7, Fig. 3).

    Delegates to the shared jnp scorer so the oracle and the SPMD engine
    produce identical scores for identical histograms.
    """
    score, split_bin = _gains(
        hist,
        total_w=np.float32(total_w),
        attr_is_cont=ds.attr_is_cont,
        n_bins=ds.n_bins,
        min_objs=cfg.min_objs,
        criterion=cfg.criterion,
    )
    return np.asarray(score), np.asarray(split_bin)


@dataclasses.dataclass
class SplitDecision:
    """Pure result of processing one node (splitPre+splitAtt+splitPost math).

    ``attr < 0`` means the node is a leaf.  Computing a decision mutates
    nothing — it is a function of (dataset, task) only — so the farm may
    retry it on any worker after a crash without corrupting the build
    (:mod:`repro.core.farm_build`).
    """

    attr: int = -1
    split_bin: int = -1                 # threshold bin (continuous), else -1
    n_children: int = 0
    child_active: np.ndarray | None = None
    child_idx: list = dataclasses.field(default_factory=list)
    child_w: list = dataclasses.field(default_factory=list)
    child_freq: list = dataclasses.field(default_factory=list)
    child_cls: list = dataclasses.field(default_factory=list)

    @property
    def is_leaf(self) -> bool:
        return self.attr < 0


def split_node(ds: BinnedDataset, cfg: GrowConfig, *, idx: np.ndarray,
               w: np.ndarray, active: np.ndarray, depth: int,
               freq: np.ndarray, cls: int) -> SplitDecision:
    """Process one node: the paper's splitPre/splitAtt/splitPost pipeline.

    Shared verbatim by the sequential oracle (:func:`build`) and the farm
    workers (:mod:`repro.core.farm_build`), so both engines make bitwise
    identical split decisions.
    """
    if split_pre(freq, depth, cfg):
        return SplitDecision()

    hist = node_histogram(ds, idx, w)
    total_w = float(w.sum())
    score, split_bin = split_att(hist, total_w, ds, cfg)
    best_attr, best_score, has_split = entropy.pick_best_attribute(
        np.asarray(score)[None, :], np.asarray(active)[None, :])
    best_attr = int(best_attr[0])
    if not bool(has_split[0]):
        return SplitDecision()

    a = best_attr
    is_cont = bool(ds.attr_is_cont[a])
    sb = int(split_bin[a])
    n_children = 2 if is_cont else int(ds.n_bins[a])

    # --- partition cases over the children (paper §2.12-14) ---------------
    b_col = ds.x[idx, a]
    known = b_col >= 0
    if is_cont:
        child_of = np.where(b_col <= sb, 0, 1)
    else:
        child_of = b_col.astype(np.int64)
    child_known_w = np.zeros(n_children, np.float64)
    np.add.at(child_known_w, child_of[known], w[known])
    w_known = float(child_known_w.sum())
    heaviest = int(np.argmax(child_known_w))

    child_idx: list[np.ndarray] = []
    child_w: list[np.ndarray] = []
    for j in range(n_children):
        sel = known & (child_of == j)
        ci, cw = idx[sel], w[sel]
        if (~known).any():
            if cfg.unknown_fractional:
                # Full C4.5: every child receives the unknown cases with
                # weight rescaled by its share of the known weight.
                share = child_known_w[j] / max(w_known, EPS_W)
                if share > 0:
                    ci = np.concatenate([ci, idx[~known]])
                    cw = np.concatenate(
                        [cw, (w[~known] * share).astype(np.float32)])
            elif j == heaviest:
                ci = np.concatenate([ci, idx[~known]])
                cw = np.concatenate([cw, w[~known]])
        child_idx.append(ci)
        child_w.append(cw.astype(np.float32))

    child_active = active.copy()
    if not is_cont:
        child_active[a] = False       # discrete attr consumed (paper §2.6)
    child_freq, child_cls = [], []
    for j in range(n_children):
        cfreq = class_frequencies(ds, child_idx[j], child_w[j]) \
            if len(child_idx[j]) else np.zeros(ds.n_classes, np.float32)
        ccls = int(np.argmax(cfreq)) if cfreq.sum() > EPS_W else int(cls)
        child_freq.append(cfreq)
        child_cls.append(ccls)
    return SplitDecision(attr=a, split_bin=sb if is_cont else -1,
                         n_children=n_children, child_active=child_active,
                         child_idx=child_idx, child_w=child_w,
                         child_freq=child_freq, child_cls=child_cls)


def build(ds: BinnedDataset, cfg: GrowConfig = GrowConfig(),
          *, task_trace: list | None = None,
          capacity: int | None = None,
          attr_mask: np.ndarray | None = None,
          case_w: np.ndarray | None = None) -> Tree:
    """Breadth-first C4.5 growth (paper Fig. 4, tree::build).

    ``task_trace``, when given, records one entry per processed node:
    ``(node_id, parent_id, r, c, n_children)`` — the exact task DAG the farm
    simulator replays (weights = r, as in the paper's WS policy).

    ``attr_mask`` (bool (A,)) restricts the split search to a subset of
    attributes and ``case_w`` (f32 (N,)) overrides the per-case weights —
    the ensemble trainer's per-tree feature-subset / bootstrap hooks
    (:mod:`repro.ensemble.sampling`).  Both default to the full dataset, so
    every engine keeps sharing one :class:`BinnedDataset` instead of
    materialising per-tree copies.
    """
    nodes = _Nodes.new()
    n = ds.n_cases
    root_idx = np.arange(n, dtype=np.int64)
    w_base = ds.w if case_w is None else np.asarray(case_w)
    root_w = w_base.astype(np.float32).copy()
    root_active = (np.ones(ds.n_attrs, dtype=bool) if attr_mask is None
                   else np.asarray(attr_mask, dtype=bool).copy())
    root_freq = class_frequencies(ds, root_idx, root_w)
    root = nodes.add(cls=int(np.argmax(root_freq)), freq=root_freq, depth=0)
    q: deque[_Task] = deque()
    q.append(_Task(root, root_idx, root_w, root_active, 0))
    parent_of = {root: -1}

    while q:
        t = q.popleft()
        dec = split_node(ds, cfg, idx=t.idx, w=t.w, active=t.active,
                         depth=t.depth, freq=nodes.freq[t.node_id],
                         cls=int(nodes.cls[t.node_id]))
        if dec.is_leaf:
            _trace(task_trace, t, parent_of, 0, ds)
            continue

        # --- emit children in sibling order (BFS ids, same as frontier) ---
        nodes.attr[t.node_id] = dec.attr
        nodes.split_bin[t.node_id] = dec.split_bin
        nodes.nchild[t.node_id] = dec.n_children
        first = None
        for j in range(dec.n_children):
            cid = nodes.add(cls=dec.child_cls[j], freq=dec.child_freq[j],
                            depth=t.depth + 1)
            parent_of[cid] = t.node_id
            if first is None:
                first = cid
            q.append(_Task(cid, dec.child_idx[j], dec.child_w[j],
                           dec.child_active, t.depth + 1))
        nodes.child0[t.node_id] = first
        _trace(task_trace, t, parent_of, dec.n_children, ds)

    return nodes.finish(ds.n_classes, capacity)


def _trace(trace: list | None, t: _Task, parent_of: dict, n_children: int,
           ds: BinnedDataset) -> None:
    if trace is not None:
        trace.append(dict(node_id=t.node_id, parent=parent_of[t.node_id],
                          r=len(t.idx), c=int(t.active.sum()),
                          n_children=n_children, depth=t.depth))
