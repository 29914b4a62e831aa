"""YaDT-FF on SPMD hardware: level-synchronous frontier tree growth.

This is the TPU-native adaptation of the paper's farm-with-feedback (see
DESIGN.md §2).  The farm's task stream becomes a *frontier* of open nodes,
drained in batches of K = ``GrowConfig.frontier_slots`` per **superstep**:

  splitPre   -> batched stop tests on stored node frequencies
  splitAtt   -> one fused (node, attr, bin, class) histogram + gain pass
                (the attribute axis is the NAP sharding axis)
  splitPost  -> batched argmax / child allocation / case re-routing
                (the synchronisation point that closes the superstep)

Because open nodes are selected in ascending id order and children are
allocated contiguously in slot order, node ids coincide exactly with the
sequential oracle's breadth-first ids — trees are comparable elementwise.
It follows that the open nodes are always the id range
``[open_nodes, n_nodes)``: a superstep takes the first K of them, closes
them, and appends their children at ``n_nodes``.  Selection is that range,
and a case's slot is its node id less ``open_nodes`` — no search, no table.

Everything is fixed-shape and jit-able; the full build is a
``lax.while_loop`` over supersteps.  The same tree can also be grown
host-side through the supervised threaded farm — :func:`build_farm` — which
tolerates worker crashes/hangs/deaths (:mod:`repro.core.farm_build`) and
stays elementwise-equal to both this engine and the sequential oracle.
The build names its phases for the profiler: every operation of the fused
program runs under one of the ``jax.named_scope`` names ``frontier.init``,
``frontier.split_pre`` (``frontier.select`` nested), ``frontier.split_att``
(``frontier.compact`` nested) and ``frontier.split_post`` (``frontier.route``
nested), and :func:`build_scopes` maps the compiled program's instructions
back to them.  These names are an interface: the benchmark reads them.
The splitAtt hot-spot is pluggable:
``impl="jnp"`` scores gains from a segment-sum histogram (reference);
``impl="pallas"`` runs the whole phase on the kernels in
:mod:`repro.kernels` — the MXU one-hot-matmul histogram (with bucketed
active-case compaction, ``GrowConfig.compact``) feeding the fused
scan/entropy split-gain kernel, tile sizes planned by
:mod:`repro.kernels.autotune`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_models, entropy
from repro.core.binning import BinnedDataset
from repro.core.config import GrowConfig
from repro.core.tree import Tree
from repro.kernels import compaction
from repro.obs import metrics as obs_metrics
from repro.obs import trace

EPS_W = entropy.EPS_W
MAX_NODES = 1 << 24         # node ids route through float32 (see route())

# A total of cases over supersteps passes 2**31 at the paper's sizes (10M
# cases x ~700 supersteps), so it is carried as (high, low) int32 words with
# LOW_BITS in the low word: exact to 2**61 without 64-bit mode.
LOW_BITS = 30
_LOW_MASK = (1 << LOW_BITS) - 1


def _wide_add(words: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """(high, low) words of a total, plus ``v`` in [0, 2**31)."""
    low = words[1] + (v & _LOW_MASK)
    return jnp.stack([words[0] + (v >> LOW_BITS) + (low >> LOW_BITS),
                      low & _LOW_MASK])


class _WideTotal:
    """A wide total's device words; ``float()`` reads them as one number."""

    __slots__ = ("words",)

    def __init__(self, words: jnp.ndarray):
        self.words = words

    def __float__(self) -> float:
        high, low = np.asarray(self.words).tolist()
        return float((high << LOW_BITS) | low)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GrowState:
    tree: Tree
    active: jnp.ndarray      # bool (M, A): attributes active at each node
    case_node: jnp.ndarray   # int32 (N,): current node of each case
    n_nodes: jnp.ndarray     # int32 scalar
    overflow: jnp.ndarray    # bool scalar — capacity forced early leaves
    # totals over the supersteps run so far (build() publishes them)
    supersteps: jnp.ndarray  # int32 scalar
    open_nodes: jnp.ndarray  # int32 scalar: open nodes processed, so the
    #                          open nodes are the ids [open_nodes, n_nodes)
    live_steps: jnp.ndarray  # int32 (2,) wide: Σ cases in an open node
    hist_steps: jnp.ndarray  # int32 (2,) wide: Σ cases the histogram got


@dataclasses.dataclass(frozen=True)
class FrontierProblem:
    """Static description of one growth problem (shapes are jit constants)."""
    n_cases: int
    n_attrs: int
    n_bins_max: int          # B: histogram bins (padded)
    n_classes: int
    max_children: int        # H: >= 2 and >= widest discrete split
    cfg: GrowConfig

    def __post_init__(self):
        if self.cfg.max_nodes > MAX_NODES:
            raise ValueError(f"max_nodes {self.cfg.max_nodes} is over "
                             f"{MAX_NODES}: routing reads node ids in "
                             "float32, exact only up to 2**24")

    @staticmethod
    def from_dataset(ds: BinnedDataset, cfg: GrowConfig) -> "FrontierProblem":
        disc = ds.n_bins[~ds.attr_is_cont]
        h = max(2, int(disc.max()) if disc.size else 2)
        return FrontierProblem(
            n_cases=ds.n_cases, n_attrs=ds.n_attrs,
            n_bins_max=max(1, ds.max_bins), n_classes=ds.n_classes,
            max_children=h, cfg=cfg)


def init_state(prob: FrontierProblem, y: jnp.ndarray, w: jnp.ndarray,
               attr_mask: jnp.ndarray | None = None) -> GrowState:
    with jax.named_scope("frontier.init"):
        return _init_state(prob, y, w, attr_mask)


def _init_state(prob, y, w, attr_mask) -> GrowState:
    cfg = prob.cfg
    tree = Tree.empty(cfg.max_nodes, prob.n_classes)
    root_freq = jax.ops.segment_sum(w.astype(jnp.float32), y,
                                    num_segments=prob.n_classes)
    tree.node_freq = tree.node_freq.at[0].set(root_freq)
    tree.node_class = tree.node_class.at[0].set(
        jnp.argmax(root_freq).astype(jnp.int32))
    active = jnp.ones((cfg.max_nodes, prob.n_attrs), bool)
    if attr_mask is not None:
        active = active & jnp.asarray(attr_mask, bool)[None, :]
    return GrowState(
        tree=tree,
        active=active,
        case_node=jnp.zeros((prob.n_cases,), jnp.int32),
        n_nodes=jnp.int32(1),
        overflow=jnp.bool_(False),
        supersteps=jnp.int32(0),
        open_nodes=jnp.int32(0),
        live_steps=jnp.zeros((2,), jnp.int32),
        hist_steps=jnp.zeros((2,), jnp.int32),
    )


# --------------------------------------------------------------------------
# Histogram pass ("splitAtt" data collection)
# --------------------------------------------------------------------------

def frontier_histogram_jnp(
    x: jnp.ndarray,            # int32 (N, A), -1 = unknown
    y: jnp.ndarray,            # int32 (N,)
    w: jnp.ndarray,            # f32 (N,)
    slot: jnp.ndarray,         # int32 (N,), -1 = not participating
    *, n_slots: int, n_bins: int, n_classes: int,
) -> jnp.ndarray:
    """(K, A, B+1, C) weighted counts; bin index B collects unknown values.

    Reference implementation: one flat segment-sum.  The Pallas kernel
    (:mod:`repro.kernels.histogram`) computes the same tensor with MXU
    one-hot matmuls and VMEM-tiled accumulation.
    """
    n, a_dim = x.shape
    k, b, c = n_slots, n_bins, n_classes
    slot_safe = jnp.where(slot >= 0, slot, k)                 # dump row
    bin_safe = jnp.where(x >= 0, x, b)                        # unknown bin
    flat = ((slot_safe[:, None] * a_dim + jnp.arange(a_dim)[None, :])
            * (b + 1) + bin_safe) * c + y[:, None]
    hist = jax.ops.segment_sum(
        jnp.broadcast_to(w[:, None], (n, a_dim)).reshape(-1),
        flat.reshape(-1),
        num_segments=(k + 1) * a_dim * (b + 1) * c)
    return hist.reshape(k + 1, a_dim, b + 1, c)[:k]


def _block_plan(prob: FrontierProblem, n_cases: int):
    from repro.kernels import autotune
    return autotune.plan_for_config(
        prob.cfg, n_cases=n_cases, n_bins=prob.n_bins_max,
        n_classes=prob.n_classes)


def _histogram(x, y, w, slot, n_live, *, prob: FrontierProblem, impl: str):
    """(K, A, B+1, C) histogram, and how many cases it was computed over:
    the compaction bucket that holds the ``n_live`` live cases, else N."""
    k = prob.cfg.frontier_slots
    n = prob.n_cases
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops
        plan = _block_plan(prob, n)
        if prob.cfg.compact:
            sizes = compaction.bucket_sizes(
                n, min_bucket=prob.cfg.compact_min_bucket)
            hist = kernel_ops.frontier_histogram_compact(
                x, y, w, slot, n_slots=k, n_bins=prob.n_bins_max,
                n_classes=prob.n_classes,
                min_bucket=prob.cfg.compact_min_bucket,
                block_t=plan.block_t, block_k=plan.block_k,
                scope="frontier.compact")
            given = jnp.asarray(sizes, jnp.int32)[
                compaction.pick_bucket(n_live, sizes)]
            return hist, given
        return kernel_ops.frontier_histogram(
            x, y, w, slot, n_slots=k, n_bins=prob.n_bins_max,
            n_classes=prob.n_classes, block_t=plan.block_t,
            block_k=plan.block_k), jnp.int32(n)
    return frontier_histogram_jnp(
        x, y, w, slot, n_slots=k, n_bins=prob.n_bins_max,
        n_classes=prob.n_classes), jnp.int32(n)


def _gains(hist, total_w, attr_is_cont, n_bins, *, prob: FrontierProblem,
           impl: str):
    """splitAtt scoring: (K, A) score/bin planes from the (K, A, B, C) hist.

    ``impl="pallas"`` runs the fused scan/entropy kernel -- one HBM read of
    the histogram.  It repeats the :mod:`repro.core.entropy` op order, so on
    a TPU its scores equal the jnp path's bit for bit for integer case
    weights.  Fractional weights and interpret mode on the CPU agree only to
    f32 rounding (:mod:`repro.kernels.split_gain`).
    """
    cfg = prob.cfg
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops
        plan = _block_plan(prob, prob.n_cases)
        return kernel_ops.split_gain(
            hist, total_w, attr_is_cont, n_bins, min_objs=cfg.min_objs,
            criterion=cfg.criterion, block_k=plan.block_k)
    return entropy.gains_from_histogram(
        hist, total_w=total_w, attr_is_cont=attr_is_cont, n_bins=n_bins,
        min_objs=cfg.min_objs, criterion=cfg.criterion)


# --------------------------------------------------------------------------
# One superstep = splitPre + splitAtt + splitPost over K open nodes.
# ``superstep`` composes the phases, each under its ``frontier.*`` scope, and
# is what the fused whole-build while_loop traces.
# --------------------------------------------------------------------------

def split_pre(state: GrowState, *, prob: FrontierProblem
              ) -> dict[str, jnp.ndarray]:
    """Frontier selection + stop tests on stored node frequencies."""
    cfg = prob.cfg
    m = cfg.max_nodes
    k = cfg.frontier_slots
    tree = state.tree

    # ---- select up to K open nodes, FIFO by id (= breadth-first) ----------
    with jax.named_scope("frontier.select"):
        ids = state.open_nodes + jnp.arange(k, dtype=jnp.int32)
        valid = ids < state.n_nodes
        ids_safe = jnp.minimum(ids, m - 1)
    # A case's slot is its node's place in the range; -1 in a closed node
    # or an open node past the first K.
    d = state.case_node - state.open_nodes
    slot = jnp.where((d >= 0) & (d < jnp.minimum(
        k, state.n_nodes - state.open_nodes)), d, -1)         # (N,)

    # ---- stop tests on stored frequencies ----------------------------------
    freq = jnp.where(valid[:, None], tree.node_freq[ids_safe], 0.0)  # (K, C)
    total_w = jnp.sum(freq, axis=-1)
    depth_k = tree.node_depth[ids_safe]
    pure = jnp.sum((freq > EPS_W).astype(jnp.int32), -1) <= 1
    small = total_w < 2.0 * cfg.min_objs
    deep = depth_k >= cfg.max_depth
    pre_leaf = pure | small | deep
    return dict(ids=ids, valid=valid, ids_safe=ids_safe, slot=slot,
                total_w=total_w, depth_k=depth_k, pre_leaf=pre_leaf)


def split_att(state: GrowState, pre: dict,
              x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
              attr_is_cont: jnp.ndarray, n_bins: jnp.ndarray,
              *, prob: FrontierProblem, impl: str) -> dict[str, jnp.ndarray]:
    """The hot phase: fused histogram + gain over (node, attribute)."""
    b_dim = prob.n_bins_max
    with jax.named_scope("frontier.compact"):
        n_live = jnp.sum((pre["slot"] >= 0).astype(jnp.int32))
    hist_u, n_hist = _histogram(x, y, w, pre["slot"], n_live, prob=prob,
                                impl=impl)
    hist = hist_u[:, :, :b_dim, :]                            # (K, A, B, C)
    unknown = hist_u[:, :, b_dim, :]                          # (K, A, C)
    score, split_bin = _gains(
        hist, pre["total_w"], attr_is_cont, n_bins,
        prob=prob, impl=impl)                                 # (K, A)
    active_k = state.active[pre["ids_safe"]] & pre["valid"][:, None]
    best_attr, best_score, has_split = entropy.pick_best_attribute(
        score, active_k)
    # (slot, attribute) pairs C4.5 scores: an open node it does not stop
    # first, an active attribute.  The histogram and the gain pass compute
    # all K x A of them.  Only the stepwise rows read this; the fused build
    # leaves it to _ScoredPairs, which counts the same pairs from the tree.
    n_tested = jnp.sum((active_k & ~pre["pre_leaf"][:, None])
                       .astype(jnp.int32))
    return dict(hist=hist, unknown=unknown, split_bin=split_bin,
                active_k=active_k, best_attr=best_attr, has_split=has_split,
                n_live=n_live, n_hist=n_hist, n_tested=n_tested)


def split_post(state: GrowState, pre: dict, att: dict,
               x: jnp.ndarray, attr_is_cont: jnp.ndarray,
               n_bins: jnp.ndarray, *, prob: FrontierProblem,
               ) -> tuple[GrowState, dict[str, jnp.ndarray]]:
    """Argmax done: allocate children, scatter results, route cases."""
    cfg = prob.cfg
    m = cfg.max_nodes
    k = cfg.frontier_slots
    a_dim, c_dim, h_dim = prob.n_attrs, prob.n_classes, prob.max_children
    tree = state.tree
    ids, valid, ids_safe = pre["ids"], pre["valid"], pre["ids_safe"]
    slot, total_w, depth_k = pre["slot"], pre["total_w"], pre["depth_k"]
    hist, unknown, active_k = att["hist"], att["unknown"], att["active_k"]
    best_attr = att["best_attr"]

    internal = valid & ~pre["pre_leaf"] & att["has_split"]
    is_cont = attr_is_cont[best_attr]
    sb = jnp.take_along_axis(att["split_bin"], best_attr[:, None], 1)[:, 0]
    nch_attr = jnp.where(is_cont, 2, n_bins[best_attr]).astype(jnp.int32)
    nch = jnp.where(internal, nch_attr, 0)

    # capacity check: if this superstep would overflow, force leaves instead
    overflow = state.n_nodes + jnp.sum(nch) > m
    internal = internal & ~overflow
    nch = jnp.where(overflow, 0, nch)
    total_children = jnp.sum(nch)
    child0 = state.n_nodes + jnp.cumsum(nch) - nch            # exclusive

    # child class frequencies (K, H, C)
    hist_best = jnp.take_along_axis(
        hist, best_attr[:, None, None, None], axis=1)[:, 0]   # (K, B, C)
    csum = jnp.cumsum(hist_best, axis=1)
    left = jnp.take_along_axis(
        csum, jnp.maximum(sb, 0)[:, None, None], axis=1)[:, 0]  # (K, C)
    known = csum[:, -1, :]
    right = known - left
    cont_freq = jnp.concatenate(
        [jnp.stack([left, right], axis=1),
         jnp.zeros((k, h_dim - 2, c_dim), jnp.float32)], axis=1)
    disc_freq = hist_best[:, :h_dim, :]
    disc_mask = (jnp.arange(h_dim)[None, :] < nch_attr[:, None])
    disc_freq = jnp.where(disc_mask[:, :, None], disc_freq, 0.0)
    child_freq = jnp.where(is_cont[:, None, None], cont_freq, disc_freq)

    # unknown-valued cases go to the heaviest child (DESIGN.md §2)
    unk = jnp.take_along_axis(unknown, best_attr[:, None, None],
                              axis=1)[:, 0]                   # (K, C)
    child_w = jnp.sum(child_freq, axis=-1)                    # (K, H)
    in_range = jnp.arange(h_dim)[None, :] < jnp.maximum(nch_attr, 1)[:, None]
    heaviest = jnp.argmax(jnp.where(in_range, child_w, -jnp.inf),
                          axis=-1).astype(jnp.int32)          # (K,)
    child_freq = child_freq + (
        jax.nn.one_hot(heaviest, h_dim, dtype=jnp.float32)[:, :, None]
        * unk[:, None, :])

    parent_class = tree.node_class[ids_safe]
    cw = jnp.sum(child_freq, axis=-1)
    child_class = jnp.where(cw > EPS_W,
                            jnp.argmax(child_freq, axis=-1),
                            parent_class[:, None]).astype(jnp.int32)

    # ---- scatter node results ----------------------------------------------
    write_ids = jnp.where(valid, ids, m)                      # m = dropped
    node_attr = jnp.where(internal, best_attr, -1)            # (K,)
    node_thr = jnp.where(internal & is_cont, sb, -1)          # (K,)
    tree = dataclasses.replace(
        tree,
        node_attr=tree.node_attr.at[write_ids].set(node_attr, mode="drop"),
        node_split_bin=tree.node_split_bin.at[write_ids].set(
            node_thr, mode="drop"),
        node_child0=tree.node_child0.at[write_ids].set(
            jnp.where(internal, child0, 0), mode="drop"),
        node_nchild=tree.node_nchild.at[write_ids].set(nch, mode="drop"),
    )

    # ---- scatter children ---------------------------------------------------
    j = jnp.arange(h_dim, dtype=jnp.int32)[None, :]           # (1, H)
    child_ids = child0[:, None] + j                           # (K, H)
    child_live = internal[:, None] & (j < nch[:, None])
    cids = jnp.where(child_live, child_ids, m)
    tree = dataclasses.replace(
        tree,
        node_class=tree.node_class.at[cids.reshape(-1)].set(
            child_class.reshape(-1), mode="drop"),
        node_freq=tree.node_freq.at[cids.reshape(-1)].set(
            child_freq.reshape(-1, c_dim), mode="drop"),
        node_depth=tree.node_depth.at[cids.reshape(-1)].set(
            jnp.broadcast_to(depth_k[:, None] + 1, (k, h_dim)).reshape(-1),
            mode="drop"),
    )
    child_active = state.active[ids_safe]                     # (K, A)
    child_active = child_active & ~(
        (~is_cont)[:, None]
        & (jnp.arange(a_dim)[None, :] == best_attr[:, None]))
    active = state.active.at[cids.reshape(-1)].set(
        jnp.broadcast_to(child_active[:, None, :],
                         (k, h_dim, a_dim)).reshape(-1, a_dim), mode="drop")

    # ---- route cases to their child (the feedback edge) --------------------
    case_node = route(state.case_node, slot, x, attr=node_attr,
                      thr=node_thr, heaviest=heaviest, child0=child0)

    n_processed = jnp.sum(valid.astype(jnp.int32))
    new_state = GrowState(
        tree=dataclasses.replace(tree, n_nodes=state.n_nodes + total_children),
        active=active, case_node=case_node,
        n_nodes=state.n_nodes + total_children,
        overflow=state.overflow | overflow,
        supersteps=state.supersteps + 1,
        open_nodes=state.open_nodes + n_processed,
        live_steps=_wide_add(state.live_steps, att["n_live"]),
        hist_steps=_wide_add(state.hist_steps, att["n_hist"]),
    )
    stats = dict(
        n_processed=n_processed,
        n_active=att["n_live"],
        n_hist=att["n_hist"],
        n_tested=att["n_tested"],
        n_internal=jnp.sum(internal.astype(jnp.int32)),
        n_children=total_children,
        max_r=jnp.max(jnp.where(valid, total_w, 0.0)),
        nap_nodes=jnp.sum(cost_models.build_att_test(
            cfg.cost_model, n_total_cases=float(prob.n_cases),
            r=total_w, c=jnp.sum(active_k, -1).astype(jnp.float32),
            alpha=cfg.alpha).astype(jnp.int32) * valid.astype(jnp.int32)),
    )
    return new_state, stats


def route(case_node: jnp.ndarray, slot: jnp.ndarray, x: jnp.ndarray, *,
          attr: jnp.ndarray, thr: jnp.ndarray, heaviest: jnp.ndarray,
          child0: jnp.ndarray) -> jnp.ndarray:
    """Each case's node after a superstep: its child where its slot split.

    The per-slot routing record is four int32 (K,) fields: ``attr`` the
    split attribute, -1 where the slot did not split (its cases keep their
    node); ``thr`` the threshold bin of a continuous split (a case goes to
    child 1 if its bin is above it), -1 for a discrete one (the bin is the
    child); ``heaviest`` the child that takes unknown values (bin -1); and
    ``child0`` the slot's first child.  ``slot`` is -1 for a case in no
    open node.
    """
    k = attr.shape[0]
    a_dim = x.shape[1]
    with jax.named_scope("frontier.route"):
        # Each case looks its slot's record up by one one-hot matmul over
        # the K slots, not by gathers.  A gather pays a fixed cost per
        # looked-up index: on a TPU v5e (N = 500,000, K = 256) six gathers
        # of per-slot fields took 23.7 ms a superstep, a compare-and-select
        # over the K slots 0.8 ms and this matmul 0.25 ms.  Its work grows
        # as N·K against the gathers' N, so it stays ahead well past any
        # configured K.  Exactly one slot matches (none where slot == -1),
        # and float32 at HIGHEST precision carries integers below 2**24
        # exactly (FrontierProblem bounds the node ids).  The record is
        # replicated and each case's column is its own, so a build sharded
        # over cases adds no collective over them.
        hit = (jnp.arange(k, dtype=jnp.int32)[:, None]
               == slot[None, :]).astype(jnp.float32)          # (K, N)
        rec = jnp.stack([attr, thr, heaviest, child0]).astype(jnp.float32)
        a_case, t_case, h_case, c_case = jnp.dot(
            rec, hit, precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
        # Row-local select of x[i, a_case[i]].  A take_along_axis here makes
        # the SPMD partitioner materialise replicated (N, 1, 2) gather
        # indices plus an all-reduce of the result — 120 MB/superstep of pure
        # routing traffic (measured).  The one-hot contraction is elementwise
        # row-local: zero collectives, A x s32 reads (A = 9).
        onehot_a = (jnp.arange(a_dim, dtype=jnp.int32)[None, :]
                    == a_case[:, None])
        b_case = jnp.sum(jnp.where(onehot_a, x, 0), axis=1)
        j_case = jnp.where(t_case >= 0, (b_case > t_case).astype(jnp.int32),
                           b_case)
        j_case = jnp.where(b_case < 0, h_case, j_case)
        return jnp.where((slot >= 0) & (a_case >= 0), c_case + j_case,
                         case_node).astype(jnp.int32)


def superstep(
    state: GrowState,
    x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
    attr_is_cont: jnp.ndarray, n_bins: jnp.ndarray,
    *, prob: FrontierProblem, impl: str = "jnp",
) -> tuple[GrowState, dict[str, jnp.ndarray]]:
    """One fused superstep: splitPre → splitAtt → splitPost."""
    with jax.named_scope("frontier.split_pre"):
        pre = split_pre(state, prob=prob)
    with jax.named_scope("frontier.split_att"):
        att = split_att(state, pre, x, y, w, attr_is_cont, n_bins,
                        prob=prob, impl=impl)
    with jax.named_scope("frontier.split_post"):
        return split_post(state, pre, att, x, attr_is_cont, n_bins,
                          prob=prob)


# --------------------------------------------------------------------------
# Full build
# --------------------------------------------------------------------------

def _superstep_fn(prob: FrontierProblem, impl: str):
    def fn(state, x, y, w, attr_is_cont, n_bins):
        return superstep(state, x, y, w, attr_is_cont, n_bins,
                         prob=prob, impl=impl)
    return fn


@functools.partial(jax.jit, static_argnames=("prob", "impl"))
def _build_jit(x, y, w, attr_mask, attr_is_cont, n_bins, *,
               prob: FrontierProblem, impl: str) -> GrowState:
    state = init_state(prob, y, w, attr_mask)
    step = _superstep_fn(prob, impl)

    def cond(state):
        with jax.named_scope("frontier.split_pre"), \
                jax.named_scope("frontier.select"):
            return state.open_nodes < state.n_nodes

    def body(state):
        new_state, _ = step(state, x, y, w, attr_is_cont, n_bins)
        return new_state

    return jax.lax.while_loop(cond, body, state)


# (prob, impl, argument shapes and dtypes) of every _build_jit program that
# build() has dispatched in this process: what build_scopes() compiles.
_DISPATCHED: dict[tuple, None] = {}


def build_scopes() -> dict[str, trace.HloScope]:
    """``{HLO instruction name: (innermost frontier.* scope, rule)}`` over
    every whole-build program :func:`build` has dispatched in this process
    (the rule that found the scope: :func:`repro.obs.trace.hlo_scopes`).

    A device trace names each operation by its HLO instruction
    (``fusion.241``); this map gives it to its phase.  The scopes are
    ``frontier.init``, ``frontier.split_pre`` > ``frontier.select``,
    ``frontier.split_att`` > ``frontier.compact`` and ``frontier.split_post``
    > ``frontier.route``; what the compiler adds outside the loop to fill
    its initial state counts as ``frontier.init``, and the loop's
    scaffolding (while, tuples, copies and prefetches the compiler adds
    inside it) may carry none.  Made on request and never on
    a build's path: each program is lowered and compiled again from its
    recorded shapes, which the compile cache turns into a load, and XLA
    names a program's instructions the same way on every compile.  Where two
    programs use one instruction name, the later program's scope wins.
    """
    out: dict[str, trace.HloScope] = {}
    for prob, impl, specs in list(_DISPATCHED):
        args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs]
        compiled = _build_jit.lower(*args, prob=prob, impl=impl).compile()
        out.update(trace.hlo_scopes(compiled.as_text(), "frontier.",
                                    entry_scope="frontier.init"))
    return out


@dataclasses.dataclass(frozen=True)
class _ScoredPairs:
    """The (slot, attribute) pairs a build's supersteps scored, counted on
    the host from its tree when ``float()`` reads them.  Each grown node
    took a slot once, and C4.5 scored it unless ``split_pre``'s stop tests
    (pure, small, deep) stopped it first, over the attributes active there:
    those ``attr_mask`` allows, less the discrete ones split on above it.
    It holds only the tree the build returned and host values, so the
    device does nothing for it and keeps nothing more alive."""

    tree: Tree
    attr_is_cont: np.ndarray   # bool (A,)
    attr_mask: Any             # bool (A,), or None for every attribute
    cfg: GrowConfig

    def __float__(self) -> float:
        t = self.tree.to_numpy()
        n = int(t.n_nodes)
        nch, attr, depth = t.node_nchild[:n], t.node_attr[:n], t.node_depth[:n]
        parent = np.zeros(n, np.int64)
        first = np.repeat(t.node_child0[:n], nch)
        parent[first + np.arange(first.size)
               - np.repeat(np.cumsum(nch) - nch, nch)] = np.repeat(
                   np.arange(n), nch)
        active = np.ones((n, self.attr_is_cont.size), bool)
        if self.attr_mask is not None:
            active[0] = np.asarray(self.attr_mask, bool)
        for d in range(1, int(depth.max(initial=0)) + 1):
            kids = np.flatnonzero(depth == d)
            up = parent[kids]
            active[kids] = active[up]
            disc = ~self.attr_is_cont[attr[up]]
            active[kids[disc], attr[up][disc]] = False
        freq = t.node_freq[:n]
        stopped = ((np.sum(freq > EPS_W, -1) <= 1)
                   | (freq.sum(-1, dtype=np.float32) < 2.0 * self.cfg.min_objs)
                   | (depth >= self.cfg.max_depth))
        return float(np.sum(active[~stopped]))


def _publish(state: GrowState, prob: FrontierProblem, scored: _ScoredPairs,
             reg: obs_metrics.Registry) -> None:
    """The one writer of the ``frontier_*`` gauges: the last build's totals,
    as device values (or its tree) that are read only when the registry is
    read."""
    reg.gauge("frontier_supersteps",
              "supersteps of the last build").set(state.supersteps)
    reg.gauge("frontier_open_nodes",
              "open nodes the last build processed").set(state.open_nodes)
    reg.gauge("frontier_live_case_steps",
              "sum over the last build's supersteps of the cases in an "
              "open node").set(_WideTotal(state.live_steps))
    reg.gauge("frontier_hist_case_steps",
              "sum over the last build's supersteps of the cases the "
              "histogram was given").set(_WideTotal(state.hist_steps))
    reg.gauge("frontier_tested_pairs",
              "sum over the last build's supersteps of the (slot, attribute) "
              "pairs C4.5 scored").set(scored)
    reg.gauge("frontier_cases",
              "training cases of the last build").set(prob.n_cases)
    reg.gauge("frontier_slots",
              "frontier slots of the last build").set(prob.cfg.frontier_slots)
    reg.gauge("frontier_attrs",
              "attributes of the last build").set(prob.n_attrs)


def build(ds: BinnedDataset, cfg: GrowConfig = GrowConfig(), *,
          impl: str = "jnp", collect_stats: bool = False,
          metrics: obs_metrics.Registry | None = None,
          attr_mask: Any = None, case_w: Any = None,
          ) -> Tree | tuple[Tree, list[dict[str, Any]]]:
    """Grow a C4.5 tree with the SPMD frontier engine.

    The build is one jitted ``while_loop`` over supersteps.  It carries its
    totals (supersteps, open nodes processed, cases in an open node and
    cases the histogram was given, summed over supersteps) and writes them,
    the (slot, attribute) pairs its supersteps scored (counted on the host
    from the tree when read), N, K and A to the ``frontier_*`` gauges of ``metrics``
    (default the process-wide :data:`repro.obs.metrics.REGISTRY`) without
    waiting for the device.  Its host work runs under the profiler
    annotations ``frontier.build`` and ``frontier.to_device`` (the copy of
    the training set that every call makes).

    With ``collect_stats=True`` the superstep loop runs host-side instead
    and also returns one row of scheduling statistics per superstep (NP vs
    NAP decisions per the configured cost model — the data behind paper
    Fig. 15; ``n_active`` and ``n_hist`` are the live cases and the cases
    the histogram was given, ``n_tested`` the (slot, attribute) pairs
    scored).

    ``attr_mask`` (bool (A,)) restricts the split search to a subset of
    attributes; ``case_w`` (f32 (N,)) overrides the per-case weights — the
    ensemble trainer's per-tree hooks (:mod:`repro.ensemble`).  Both are
    traced arguments, so forests of masked/bootstrapped trees reuse one
    compiled build.
    """
    if cfg.unknown_fractional:
        raise ValueError("frontier engine routes unknowns to the heaviest "
                         "child; use the c45 oracle for fractional semantics")
    prob = FrontierProblem.from_dataset(ds, cfg)
    with trace.annotation("frontier.build"):
        with trace.annotation("frontier.to_device"):
            x = jnp.asarray(ds.x)
            y = jnp.asarray(ds.y)
            w = jnp.asarray(ds.w if case_w is None else case_w, jnp.float32)
            mask = (jnp.ones((ds.n_attrs,), bool) if attr_mask is None
                    else jnp.asarray(attr_mask, bool))
            cont = jnp.asarray(ds.attr_is_cont)
            nb = jnp.asarray(ds.n_bins, jnp.int32)
        args = (x, y, w, mask, cont, nb)
        rows: list[dict[str, Any]] = []
        if collect_stats:
            fused = jax.jit(_superstep_fn(prob, impl))
            state = init_state(prob, y, w, mask)
            while bool(state.open_nodes < state.n_nodes):
                state, stats = fused(state, x, y, w, cont, nb)
                rows.append({k: np.asarray(v).item()
                             for k, v in stats.items()})
        else:
            _DISPATCHED.setdefault(
                (prob, impl, tuple((a.shape, a.dtype) for a in args)))
            state = _build_jit(*args, prob=prob, impl=impl)
        tree = dataclasses.replace(state.tree, n_nodes=state.n_nodes)
        _publish(state, prob,
                 _ScoredPairs(tree, np.asarray(ds.attr_is_cont, bool),
                              attr_mask, cfg),
                 obs_metrics.REGISTRY if metrics is None else metrics)
    return (tree, rows) if collect_stats else tree


def build_farm(ds: BinnedDataset, cfg: GrowConfig = GrowConfig(), **kw):
    """Grow the same tree through the supervised *threaded* farm.

    The host-side, fault-tolerant counterpart of :func:`build`: workers may
    crash, hang past ``FaultPolicy.task_deadline`` or die permanently and
    the result is still elementwise-equal to the oracle (and hence to the
    SPMD engine).  See :func:`repro.core.farm_build.build` for the keyword
    surface (``n_workers``, ``fault``, ``injector``, ``policy``, ...).
    """
    from repro.core import farm_build
    return farm_build.build(ds, cfg, **kw)
