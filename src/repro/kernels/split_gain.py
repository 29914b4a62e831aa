"""Pallas TPU kernel: fused split-gain scoring ("splitAtt" compute phase).

Given the frontier histogram ``(K, A, B, C)`` this kernel fuses, per
(attribute, slot block) and entirely in VMEM:

  * the bin prefix-scan (left/right partition counts),
  * the C4.5 entropy/gain evaluation of every candidate threshold
    (continuous) or of the multiway split (discrete),
  * the known-fraction F scaling and MINOBJS validity masks,
  * the argmax over candidate bins.

One HBM read of the histogram produces the two tiny (K, A) result planes.

Layout: each block is ``(C, Bg, Kblk)`` -- classes on the leading axis,
bins on sublanes, slots on lanes -- so the class sums of the entropy terms
are elementwise adds and the bin scan and argmax run down sublanes.  The
prefix scan is a lower-triangular ones matrix times the counts on the MXU;
the triangle is 0/1 and the dot runs at ``Precision.HIGHEST``, so prefix
sums of integer counts below 2**24 are exact.

The formulas are those of :func:`repro.core.entropy.gains_from_histogram`,
which stays the oracle, and so is the op order: ``entropy.log2``, sums over
classes and over a discrete attribute's children left to right, the same
divisions in the same place.  On a TPU, XLA and Mosaic then round every
step alike, so for integer case weights (exact prefix sums) the scores, the
split bins and the trees equal the jnp path's bit for bit.  Interpret mode
on the CPU compiles the two sides into different XLA fusions, which may
round differently (see ``tests/test_frontier_pallas_path.py`` for the bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.entropy import EPS_W, NEG_INF, _xlogx, log2
from repro.kernels import autotune


def _class_sums(n_classes, terms):
    """Left-to-right sums over classes of ``terms(c)``, a tuple of arrays
    (the order of :func:`repro.core.entropy.ordered_sum`)."""
    def body(c, acc):
        return tuple(x + y for x, y in zip(acc, terms(c)))
    return jax.lax.fori_loop(1, n_classes, body, terms(0))


def _gain_kernel(meta_ref, hist_ref, tw_ref, score_ref, bin_ref, *,
                 min_objs: float, criterion: str):
    a = pl.program_id(0)
    is_cont = meta_ref[a, 0] != 0
    n_bins = meta_ref[a, 1]
    n_classes, bg, kb = hist_ref.shape
    tw = tw_ref[...]                                        # (1, Kb)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bg, kb), 0)
    f_den = jnp.where(tw > EPS_W, tw, 1.0)

    def write(score, split_bin):
        score_ref[...] = score
        bin_ref[...] = split_bin

    @pl.when(is_cont)
    def _continuous():
        tri = (jax.lax.broadcasted_iota(jnp.int32, (bg, bg), 1)
               <= jax.lax.broadcasted_iota(jnp.int32, (bg, bg), 0)
               ).astype(jnp.float32)

        def terms(c):
            left = jax.lax.dot_general(
                tri, hist_ref[c], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)       # (Bg, Kb) cumsum
            known = left[bg - 1:bg, :]                    # (1, Kb)
            right = known - left
            return (known, _xlogx(known), left, _xlogx(left), right,
                    _xlogx(right))

        w_known, s_known, wl, s_left, wr, s_right = _class_sums(
            n_classes, terms)
        safe_w = jnp.where(w_known > EPS_W, w_known, 1.0)
        info_parent = jnp.maximum(_xlogx(w_known) - s_known, 0.0)
        info_lr = (jnp.maximum(_xlogx(wl) - s_left, 0.0)
                   + jnp.maximum(_xlogx(wr) - s_right, 0.0))
        gain = (info_parent - info_lr) / safe_w
        gain = (w_known / f_den) * gain
        if criterion == "gain_ratio":
            w = wl + wr
            safe = jnp.where(w > EPS_W, w, 1.0)
            ent = log2(safe) - (_xlogx(wl) + _xlogx(wr)) / safe
            denom = jnp.where(w > EPS_W, jnp.maximum(ent, 0.0), 0.0)
            gain = jnp.where(denom > EPS_W, gain / denom, 0.0)
        valid = (rows < n_bins - 1) & (wl >= min_objs) & (wr >= min_objs)
        score = jnp.where(valid, gain, NEG_INF)
        best = jnp.max(score, axis=0, keepdims=True)
        first = jnp.min(jnp.where(score == best, rows, bg), axis=0,
                        keepdims=True)                    # first max
        write(best, first)

    @pl.when(jnp.logical_not(is_cont))
    def _discrete():
        structural = rows < n_bins

        def terms(c):
            h = jnp.where(structural, hist_ref[c], 0.0)
            parent = jnp.sum(h, axis=0, keepdims=True)  # integers: exact
            return parent, _xlogx(parent)

        w_known, s_parent = _class_sums(n_classes, terms)

        def child(i, acc):
            # child i (bin row i): its entropy term and x*log2(x) of its
            # weight, added to the running sums in bin order
            def terms_i(c):
                h = hist_ref[c, pl.ds(i, 1), :]
                return h, _xlogx(h)
            w_i, s_i = _class_sums(n_classes, terms_i)
            info_i = jnp.maximum(_xlogx(w_i) - s_i, 0.0)
            return (acc[0] + info_i, acc[1] + _xlogx(w_i), acc[2] + w_i,
                    acc[3] + (w_i >= min_objs).astype(jnp.int32))

        row0 = jnp.zeros((1, kb), jnp.float32)
        info_children, s_children, total, branches = jax.lax.fori_loop(
            0, n_bins, child,
            (row0, row0, row0, jnp.zeros((1, kb), jnp.int32)))
        safe_w = jnp.where(w_known > EPS_W, w_known, 1.0)
        info_parent = jnp.maximum(_xlogx(w_known) - s_parent, 0.0)
        gain = (info_parent - info_children) / safe_w
        gain = (w_known / f_den) * gain
        gain = jnp.where(w_known > EPS_W, jnp.maximum(gain, 0.0), 0.0)
        if criterion == "gain_ratio":
            safe_t = jnp.where(total > EPS_W, total, 1.0)
            ent = log2(safe_t) - s_children / safe_t
            denom = jnp.where(total > EPS_W, jnp.maximum(ent, 0.0), 0.0)
            gain = jnp.where(denom > EPS_W, gain / denom, 0.0)
        write(jnp.where(branches >= 2, gain, NEG_INF),
              jnp.full((1, kb), -1, jnp.int32))


@functools.partial(
    jax.jit,
    static_argnames=("min_objs", "criterion", "block_k", "interpret"))
def split_gain(
    hist: jnp.ndarray,          # f32 (K, A, B, C)
    total_w: jnp.ndarray,       # f32 (K,)
    attr_is_cont: jnp.ndarray,  # bool (A,)
    n_bins: jnp.ndarray,        # int32 (A,)
    *,
    min_objs: float = 2.0,
    criterion: str = "gain",
    block_k: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(score, split_bin)`` of shape (K, A); score -inf = invalid.

    ``block_k`` is a lane tile: a multiple of 128, or at least K.
    """
    if criterion not in ("gain", "gain_ratio"):
        raise ValueError(f"unknown criterion: {criterion!r}")
    k, a_dim, b_dim, c_dim = hist.shape
    bg = autotune.round_up(b_dim, 8)
    block_k = autotune.lane_block(block_k, k)
    kp = autotune.round_up(k, block_k)
    h = jnp.pad(hist.astype(jnp.float32).transpose(1, 3, 2, 0),
                ((0, 0), (0, 0), (0, bg - b_dim), (0, kp - k)))  # (A,C,Bg,Kp)
    tw = jnp.pad(total_w.astype(jnp.float32), (0, kp - k))[None, :]
    meta = jnp.stack([attr_is_cont.astype(jnp.int32),
                      n_bins.astype(jnp.int32)], axis=1)         # (A, 2)

    score, split_bin = pl.pallas_call(
        functools.partial(_gain_kernel, min_objs=float(min_objs),
                          criterion=criterion),
        grid=(a_dim, kp // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, c_dim, bg, block_k),
                         lambda a, kb: (a, 0, 0, kb)),
            pl.BlockSpec((1, block_k), lambda a, kb: (0, kb)),
        ],
        out_specs=(
            pl.BlockSpec((None, 1, block_k), lambda a, kb: (a, 0, kb)),
            pl.BlockSpec((None, 1, block_k), lambda a, kb: (a, 0, kb)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((a_dim, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((a_dim, 1, kp), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=autotune.VMEM_LIMIT),
        interpret=interpret,
        name="split_gain",
    )(meta, h, tw)
    return score[:, 0, :k].T, split_bin[:, 0, :k].T
