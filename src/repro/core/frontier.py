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

Everything is fixed-shape and jit-able; the full build is a
``lax.while_loop`` over supersteps.  The same tree can also be grown
host-side through the supervised threaded farm — :func:`build_farm` — which
tolerates worker crashes/hangs/deaths (:mod:`repro.core.farm_build`) and
stays elementwise-equal to both this engine and the sequential oracle.
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
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_models, entropy
from repro.core.binning import BinnedDataset
from repro.core.config import GrowConfig
from repro.core.tree import Tree

EPS_W = entropy.EPS_W


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GrowState:
    tree: Tree
    status: jnp.ndarray      # int32 (M,): 0 empty, 1 open, 2 internal, 3 leaf
    active: jnp.ndarray      # bool (M, A): attributes active at each node
    case_node: jnp.ndarray   # int32 (N,): current node of each case
    n_nodes: jnp.ndarray     # int32 scalar
    overflow: jnp.ndarray    # bool scalar — capacity forced early leaves

    STATUS_EMPTY = 0
    STATUS_OPEN = 1
    STATUS_INTERNAL = 2
    STATUS_LEAF = 3


@dataclasses.dataclass(frozen=True)
class FrontierProblem:
    """Static description of one growth problem (shapes are jit constants)."""
    n_cases: int
    n_attrs: int
    n_bins_max: int          # B: histogram bins (padded)
    n_classes: int
    max_children: int        # H: >= 2 and >= widest discrete split
    cfg: GrowConfig

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
        status=jnp.zeros((cfg.max_nodes,), jnp.int32).at[0].set(
            GrowState.STATUS_OPEN),
        active=active,
        case_node=jnp.zeros((prob.n_cases,), jnp.int32),
        n_nodes=jnp.int32(1),
        overflow=jnp.bool_(False),
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


def _histogram(x, y, w, slot, *, prob: FrontierProblem, impl: str):
    k = prob.cfg.frontier_slots
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops
        plan = _block_plan(prob, prob.n_cases)
        if prob.cfg.compact:
            return kernel_ops.frontier_histogram_compact(
                x, y, w, slot, n_slots=k, n_bins=prob.n_bins_max,
                n_classes=prob.n_classes,
                min_bucket=prob.cfg.compact_min_bucket,
                block_t=plan.block_t, block_k=plan.block_k)
        return kernel_ops.frontier_histogram(
            x, y, w, slot, n_slots=k, n_bins=prob.n_bins_max,
            n_classes=prob.n_classes, block_t=plan.block_t,
            block_k=plan.block_k)
    return frontier_histogram_jnp(
        x, y, w, slot, n_slots=k, n_bins=prob.n_bins_max,
        n_classes=prob.n_classes)


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
# The phases are separate jit-able functions so the observability path
# (build(collect_stats=True, tracer=...)) can time each one; ``superstep``
# composes them and is what the fused whole-build while_loop traces.
# --------------------------------------------------------------------------

def split_pre(state: GrowState, *, prob: FrontierProblem
              ) -> dict[str, jnp.ndarray]:
    """Frontier selection + stop tests on stored node frequencies."""
    cfg = prob.cfg
    m = cfg.max_nodes
    k = cfg.frontier_slots
    tree = state.tree

    # ---- select up to K open nodes, FIFO by id (= breadth-first) ----------
    ids = jnp.nonzero(state.status == GrowState.STATUS_OPEN,
                      size=k, fill_value=m)[0].astype(jnp.int32)
    valid = ids < m
    ids_safe = jnp.minimum(ids, m - 1)

    node_to_slot = jnp.full((m + 1,), -1, jnp.int32).at[ids].set(
        jnp.arange(k, dtype=jnp.int32), mode="drop")
    slot = node_to_slot[state.case_node]                      # (N,)

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
    from repro.sharding.act import shard_frontier_hist
    hist_u = shard_frontier_hist(
        _histogram(x, y, w, pre["slot"], prob=prob, impl=impl))  # (K,A,B+1,C)
    hist = hist_u[:, :, :b_dim, :]
    unknown = hist_u[:, :, b_dim, :]                          # (K, A, C)
    score, split_bin = _gains(
        hist, pre["total_w"], attr_is_cont, n_bins,
        prob=prob, impl=impl)                                 # (K, A)
    active_k = state.active[pre["ids_safe"]] & pre["valid"][:, None]
    best_attr, best_score, has_split = entropy.pick_best_attribute(
        score, active_k)
    return dict(hist=hist, unknown=unknown, split_bin=split_bin,
                active_k=active_k, best_attr=best_attr, has_split=has_split)


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
    tree = dataclasses.replace(
        tree,
        node_attr=tree.node_attr.at[write_ids].set(
            jnp.where(internal, best_attr, -1), mode="drop"),
        node_split_bin=tree.node_split_bin.at[write_ids].set(
            jnp.where(internal & is_cont, sb, -1), mode="drop"),
        node_child0=tree.node_child0.at[write_ids].set(
            jnp.where(internal, child0, 0), mode="drop"),
        node_nchild=tree.node_nchild.at[write_ids].set(nch, mode="drop"),
    )
    status = state.status.at[write_ids].set(
        jnp.where(internal, GrowState.STATUS_INTERNAL, GrowState.STATUS_LEAF),
        mode="drop")

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
    status = status.at[cids.reshape(-1)].set(GrowState.STATUS_OPEN,
                                             mode="drop")
    child_active = state.active[ids_safe]                     # (K, A)
    child_active = child_active & ~(
        (~is_cont)[:, None]
        & (jnp.arange(a_dim)[None, :] == best_attr[:, None]))
    active = state.active.at[cids.reshape(-1)].set(
        jnp.broadcast_to(child_active[:, None, :],
                         (k, h_dim, a_dim)).reshape(-1, a_dim), mode="drop")

    # ---- route cases to their child (the feedback edge) --------------------
    part = slot >= 0
    slot_safe = jnp.maximum(slot, 0)
    a_case = best_attr[slot_safe]
    # Row-local select of x[i, a_case[i]].  A take_along_axis here makes the
    # SPMD partitioner materialise replicated (N, 1, 2) gather indices plus
    # an all-reduce of the result — 120 MB/superstep of pure routing traffic
    # (measured).  The one-hot contraction is elementwise row-local: zero
    # collectives, A x s32 reads (A = 9).
    onehot_a = (jnp.arange(a_dim, dtype=jnp.int32)[None, :]
                == a_case[:, None])
    b_case = jnp.sum(jnp.where(onehot_a, x, 0), axis=1)
    j_cont = jnp.where(b_case <= sb[slot_safe], 0, 1)
    j_case = jnp.where(is_cont[slot_safe], j_cont, b_case)
    j_case = jnp.where(b_case < 0, heaviest[slot_safe], j_case)
    new_node = child0[slot_safe] + j_case
    case_node = jnp.where(part & internal[slot_safe], new_node,
                          state.case_node).astype(jnp.int32)

    new_state = GrowState(
        tree=dataclasses.replace(tree, n_nodes=state.n_nodes + total_children),
        status=status, active=active, case_node=case_node,
        n_nodes=state.n_nodes + total_children,
        overflow=state.overflow | overflow,
    )
    stats = dict(
        n_processed=jnp.sum(valid.astype(jnp.int32)),
        n_active=jnp.sum((slot >= 0).astype(jnp.int32)),
        n_internal=jnp.sum(internal.astype(jnp.int32)),
        n_children=total_children,
        max_r=jnp.max(jnp.where(valid, total_w, 0.0)),
        nap_nodes=jnp.sum(cost_models.build_att_test(
            cfg.cost_model, n_total_cases=float(prob.n_cases),
            r=total_w, c=jnp.sum(active_k, -1).astype(jnp.float32),
            alpha=cfg.alpha).astype(jnp.int32) * valid.astype(jnp.int32)),
    )
    return new_state, stats


def superstep(
    state: GrowState,
    x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
    attr_is_cont: jnp.ndarray, n_bins: jnp.ndarray,
    *, prob: FrontierProblem, impl: str = "jnp",
) -> tuple[GrowState, dict[str, jnp.ndarray]]:
    """One fused superstep: splitPre → splitAtt → splitPost."""
    pre = split_pre(state, prob=prob)
    att = split_att(state, pre, x, y, w, attr_is_cont, n_bins,
                    prob=prob, impl=impl)
    return split_post(state, pre, att, x, attr_is_cont, n_bins, prob=prob)


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
        return jnp.any(state.status == GrowState.STATUS_OPEN)

    def body(state):
        new_state, _ = step(state, x, y, w, attr_is_cont, n_bins)
        return new_state

    return jax.lax.while_loop(cond, body, state)


def build(ds: BinnedDataset, cfg: GrowConfig = GrowConfig(), *,
          impl: str = "jnp", collect_stats: bool = False,
          tracer: Any = None, metrics: Any = None,
          attr_mask: Any = None, case_w: Any = None,
          ) -> Tree | tuple[Tree, list[dict[str, Any]]]:
    """Grow a C4.5 tree with the SPMD frontier engine.

    With ``collect_stats=True`` the superstep loop runs host-side and returns
    per-superstep scheduling statistics (NP vs NAP decisions per the
    configured cost model — the data behind paper Fig. 15); the per-step
    ``n_active``/``nap_nodes``/... values also flow into the metrics
    registry (``metrics``, default the process-wide one).

    With an *enabled* ``tracer`` (:class:`repro.obs.trace.Tracer`) the loop
    additionally runs the three phases as separately jitted, synchronously
    timed steps, so the exported trace shows real splitPre / splitAtt /
    splitPost wall time per superstep.  With tracing disabled nothing
    changes: the fused single-jit superstep (or the whole-build
    ``while_loop``) runs exactly as before.

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
    x = jnp.asarray(ds.x)
    y = jnp.asarray(ds.y)
    w = jnp.asarray(ds.w if case_w is None else case_w, jnp.float32)
    mask = (jnp.ones((ds.n_attrs,), bool) if attr_mask is None
            else jnp.asarray(attr_mask, bool))
    cont = jnp.asarray(ds.attr_is_cont)
    nb = jnp.asarray(ds.n_bins, jnp.int32)
    traced = tracer is not None and tracer.enabled

    if not collect_stats and not traced:
        state = _build_jit(x, y, w, mask, cont, nb, prob=prob, impl=impl)
        return dataclasses.replace(state.tree, n_nodes=state.n_nodes)

    from repro.obs import metrics as obs_metrics
    reg = metrics if metrics is not None else obs_metrics.REGISTRY
    m_steps = reg.counter("frontier_supersteps_total")
    m_active = reg.gauge("frontier_active_cases")
    m_open = reg.gauge("frontier_open_nodes")
    m_nap = reg.counter("frontier_nap_nodes_total")
    m_children = reg.counter("frontier_children_total")
    m_phase = reg.histogram("frontier_phase_seconds",
                            "per-phase superstep wall time, phase= label")

    if traced:
        pre_j = jax.jit(functools.partial(split_pre, prob=prob))
        att_j = jax.jit(functools.partial(split_att, prob=prob, impl=impl))
        post_j = jax.jit(functools.partial(split_post, prob=prob))

        def timed_phase(name, fn, *args):
            t0 = time.perf_counter()
            with tracer.span(name):
                out = jax.block_until_ready(fn(*args))
            m_phase.observe(time.perf_counter() - t0, phase=name)
            return out

        def step_fn(state, step_i):
            with tracer.span("superstep", step=step_i):
                pre = timed_phase("splitPre", pre_j, state)
                att = timed_phase("splitAtt", att_j, state, pre,
                                  x, y, w, cont, nb)
                return timed_phase("splitPost", post_j, state, pre, att,
                                   x, cont, nb)
    else:
        fused = jax.jit(_superstep_fn(prob, impl))

        def step_fn(state, step_i):
            return fused(state, x, y, w, cont, nb)

    state = init_state(prob, y, w, mask)
    out: list[dict[str, Any]] = []
    step_i = 0
    while bool(jnp.any(state.status == GrowState.STATUS_OPEN)):
        state, stats = step_fn(state, step_i)
        row = {k: np.asarray(v).item() for k, v in stats.items()}
        out.append(row)
        m_steps.inc()
        m_active.set(row["n_active"])
        m_open.set(row["n_processed"])
        m_nap.inc(row["nap_nodes"])
        m_children.inc(row["n_children"])
        if traced:
            tracer.counter("frontier.n_active", value=row["n_active"])
        step_i += 1
    tree = dataclasses.replace(state.tree, n_nodes=state.n_nodes)
    if not collect_stats:
        return tree
    return tree, out


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
