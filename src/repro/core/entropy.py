"""C4.5 entropy / information-gain math, shared by every engine.

This module is the single source of truth for the split-scoring formulas of
the paper (Sect. 3.1, footnote 3):

    info(S)   = - sum_j  freq(c_j, S)/|S| * log2(freq(c_j, S)/|S|)
    gain(T, T_1..T_h) = info(T) - sum_i |T_i|/|T| * info(T_i)

with C4.5's unknown-value correction: frequencies are *weighted* counts over
cases with a known value for the tested attribute, and the gain is scaled by
the known fraction ``F = W_known / W_total``.

The same functions are called by

  * the sequential YaDT oracle (``core/c45.py``),
  * the vectorized frontier engine (``core/frontier.py``),
  * the Pallas kernel oracle (``kernels/ref.py``),

so that split decisions are bitwise comparable across engines (identical op
order on identical histogram tensors).  The Pallas split-gain kernel
(``kernels/split_gain.py``) repeats the same op sequence per VMEM block, so
on a TPU its scores equal these bit for bit for integer case weights.  Two
rules keep XLA and Mosaic in step: ``log2`` is ``log(x) * LOG2_E`` (XLA
rewrites ``jnp.log2``'s ``log(x) / log(2)`` differently from Mosaic, up to
2 ulp apart on a v5e), and every sum of entropy terms runs left to right
(:func:`ordered_sum`), never as a reduction whose association the compiler
picks.  Sums of integer weights are exact in any order.

All functions are pure jnp, dtype-stable (float32 by default), and batched:
leading dimensions are arbitrary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# A weighted count below EPS_W is treated as an empty partition.
EPS_W = 1e-7
# Gains below EPS_GAIN are treated as "no information" (C4.5 uses a tiny
# positive epsilon so that FP noise never drives a split).
EPS_GAIN = 1e-6

NEG_INF = float("-inf")  # Python literal: safe to close over in Pallas kernels
LOG2_E = 1.4426950408889634


def log2(x: jnp.ndarray) -> jnp.ndarray:
    """log2 as ``log(x) * LOG2_E``: one log and one multiply, which XLA and
    Mosaic both evaluate to the same bits on a TPU."""
    return jnp.log(x) * LOG2_E


def ordered_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Sum along ``axis`` strictly left to right: ``((x0 + x1) + x2) + ...``.

    The association is fixed, so every engine (and the Pallas kernel, which
    adds in the same order) rounds the same way.
    """
    x = jnp.moveaxis(x, axis, 0)
    acc = x[0]
    for i in range(1, x.shape[0]):
        acc = acc + x[i]
    return acc


def _xlogx(p: jnp.ndarray) -> jnp.ndarray:
    """x * log2(x), continuously extended with 0 at x == 0."""
    safe = jnp.where(p > 0, p, 1.0)
    return jnp.where(p > 0, p * log2(safe), 0.0)


def info(counts: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Entropy (bits) of a weighted class-count vector.

    ``info(S) = log2(W) - (1/W) * sum_c n_c log2 n_c`` with ``W = sum_c n_c``.
    Empty count vectors yield 0.  ``counts`` may have any leading batch shape.
    """
    counts = counts.astype(jnp.float32)
    w = jnp.sum(counts, axis=axis)
    safe_w = jnp.where(w > EPS_W, w, 1.0)
    s = ordered_sum(_xlogx(counts), axis=axis)
    ent = log2(safe_w) - s / safe_w
    return jnp.where(w > EPS_W, jnp.maximum(ent, 0.0), 0.0)


def weighted_info(counts: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """``W * info`` — the un-normalised entropy term ``W*log2(W) - sum n log n``.

    Summing ``weighted_info`` of children and dividing by the parent weight
    avoids one division per child and is the form used inside the kernels.
    """
    counts = counts.astype(jnp.float32)
    w = jnp.sum(counts, axis=axis)
    return jnp.maximum(_xlogx(w) - ordered_sum(_xlogx(counts), axis=axis),
                       0.0)


def split_gain_from_children(
    child_counts: jnp.ndarray,
    *,
    total_w: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Information gain of a partition.

    Args:
      child_counts: ``(..., H, C)`` weighted class counts per child.  The
        parent (known-valued) counts are the sum over ``H``.
      total_w: optional ``(...)`` total node weight *including* cases whose
        value for the attribute is unknown; the gain is scaled by the known
        fraction ``F = W_known / W_total`` (C4.5 unknown correction).  When
        None, ``F = 1``.

    Returns:
      ``(...)`` gain in bits (>= 0 up to FP noise).
    """
    parent = jnp.sum(child_counts, axis=-2)
    w_known = jnp.sum(parent, axis=-1)
    safe_w = jnp.where(w_known > EPS_W, w_known, 1.0)
    info_parent = weighted_info(parent)                       # W_k * info
    info_children = ordered_sum(weighted_info(child_counts), axis=-1)
    gain = (info_parent - info_children) / safe_w
    if total_w is not None:
        f = w_known / jnp.where(total_w > EPS_W, total_w, 1.0)
        gain = f * gain
    return jnp.where(w_known > EPS_W, jnp.maximum(gain, 0.0), 0.0)


def split_info(child_counts: jnp.ndarray) -> jnp.ndarray:
    """C4.5 split-info (denominator of the gain ratio) over children weights."""
    w_children = jnp.sum(child_counts, axis=-1)               # (..., H)
    return info(w_children, axis=-1)


def fayyad_irani_mask(hist: jnp.ndarray) -> jnp.ndarray:
    """Boundary-point candidate mask (YaDT's Fayyad–Irani optimisation).

    A cut between bins ``b`` and ``b+1`` can only maximise information gain
    at a *boundary point*: skip it when the nearest non-empty bin on each
    side is pure and both carry the same class (F&I 1992, Theorem 1 — the
    gain there is dominated by an adjacent boundary cut, so masking never
    changes the selected split; property-tested in tests/test_entropy.py).

    hist: (..., B, C) -> bool (..., B); True = evaluate the cut after bin b.
    """
    hist = hist.astype(jnp.float32)
    b_dim = hist.shape[-2]
    nonzero = jnp.sum(hist, -1) > EPS_W                     # (..., B)
    pure = jnp.sum((hist > EPS_W).astype(jnp.int32), -1) == 1
    cls = jnp.argmax(hist, -1)
    idx = jnp.arange(b_dim)

    # nearest non-empty bin at-or-before b / strictly-after b
    ax = nonzero.ndim - 1                 # lax.cummax rejects negative axes
    last = jax.lax.cummax(jnp.where(nonzero, idx, -1), axis=ax)
    nxt_rev = jax.lax.cummax(
        jnp.where(jnp.flip(nonzero, -1), idx, -1), axis=ax)
    at_or_after = (b_dim - 1) - jnp.flip(nxt_rev, -1)       # smallest i >= b
    nxt = jnp.concatenate(                                  # smallest i > b
        [at_or_after[..., 1:],
         jnp.full(at_or_after.shape[:-1] + (1,), b_dim,
                  at_or_after.dtype)], axis=-1)

    def take(a, i, fill):
        safe = jnp.clip(i, 0, b_dim - 1)
        v = jnp.take_along_axis(a, safe, axis=-1)
        return v, (i >= 0) & (i <= b_dim - 1)

    l_pure, l_ok = take(pure, last, False)
    l_cls, _ = take(cls, last, 0)
    r_pure, r_ok = take(pure, nxt, False)
    r_cls, _ = take(cls, nxt, 0)
    non_boundary = (l_ok & r_ok & l_pure & r_pure & (l_cls == r_cls))
    return ~non_boundary


def gains_for_continuous(
    hist: jnp.ndarray,
    *,
    total_w: jnp.ndarray,
    n_bins: jnp.ndarray,
    min_objs: float = 2.0,
    criterion: str = "gain",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Best binary split of a continuous attribute from its bin histogram.

    Scans every candidate threshold ``value <= edge[b]`` for ``b`` in
    ``[0, n_bins-2]`` — in EC4.5 rank space the bins *are* the sorted domain
    values of the whole training set, so the candidate set coincides with the
    C4.5 midpoint set and the selected edge is automatically "the greatest
    value of A in the whole training set below the local threshold"
    (paper §2.9-10 / EC4.5 binary search).

    Args:
      hist: ``(..., B, C)`` weighted (bin, class) counts of known-valued cases.
      total_w: ``(...)`` total node weight (for the F scaling).
      n_bins: ``(...)`` or scalar — actual number of bins of this attribute
        (bins >= n_bins are structural padding and must be empty).
      min_objs: C4.5 MINOBJS — both sides of a valid split must carry at
        least this much weight.
      criterion: ``"gain"`` (paper semantics) or ``"gain_ratio"``.

    Returns:
      ``best_score (...)`` (-inf when no valid candidate) and
      ``best_bin (...)`` int32 — the split is ``bin <= best_bin``.
    """
    hist = hist.astype(jnp.float32)
    b_dim = hist.shape[-2]
    left = jnp.cumsum(hist, axis=-2)                          # (..., B, C)
    known = left[..., -1, :]                                  # (..., C)
    right = known[..., None, :] - left                        # (..., B, C)

    w_known = jnp.sum(known, axis=-1)                         # (...)
    safe_w = jnp.where(w_known > EPS_W, w_known, 1.0)
    wl = jnp.sum(left, axis=-1)                               # (..., B)
    wr = jnp.sum(right, axis=-1)

    info_parent = weighted_info(known)                        # (...)
    info_lr = weighted_info(left) + weighted_info(right)      # (..., B)
    gain = (info_parent[..., None] - info_lr) / safe_w[..., None]
    f = w_known / jnp.where(total_w > EPS_W, total_w, 1.0)
    gain = f[..., None] * gain

    if criterion == "gain_ratio":
        denom = info(jnp.stack([wl, wr], axis=-1), axis=-1)
        gain = jnp.where(denom > EPS_W, gain / denom, 0.0)
    elif criterion != "gain":
        raise ValueError(f"unknown criterion: {criterion!r}")

    bins = jnp.arange(b_dim, dtype=jnp.int32)
    n_bins = jnp.asarray(n_bins, dtype=jnp.int32)
    structural = bins < jnp.expand_dims(n_bins - 1, -1) if n_bins.ndim else (
        bins < n_bins - 1)
    valid = structural & (wl >= min_objs) & (wr >= min_objs)
    score = jnp.where(valid, gain, NEG_INF)
    best_bin = jnp.argmax(score, axis=-1).astype(jnp.int32)   # first max
    best_score = jnp.max(score, axis=-1)
    return best_score, best_bin


def gains_for_discrete(
    hist: jnp.ndarray,
    *,
    total_w: jnp.ndarray,
    n_bins: jnp.ndarray,
    min_objs: float = 2.0,
    criterion: str = "gain",
) -> jnp.ndarray:
    """Score of the h-way split of a discrete attribute (one child per value).

    Valid only when at least two branches carry >= min_objs weight (C4.5).
    Returns ``(...)`` score, -inf when invalid.
    """
    hist = hist.astype(jnp.float32)
    b_dim = hist.shape[-2]
    bins = jnp.arange(b_dim, dtype=jnp.int32)
    n_bins = jnp.asarray(n_bins, dtype=jnp.int32)
    structural = bins < jnp.expand_dims(n_bins, -1) if n_bins.ndim else (
        bins < n_bins)
    hist = jnp.where(structural[..., None], hist, 0.0)

    gain = split_gain_from_children(hist, total_w=total_w)
    if criterion == "gain_ratio":
        denom = split_info(hist)
        gain = jnp.where(denom > EPS_W, gain / denom, 0.0)

    w_children = jnp.sum(hist, axis=-1)                       # (..., B)
    branches = jnp.sum((w_children >= min_objs).astype(jnp.int32), axis=-1)
    valid = branches >= 2
    return jnp.where(valid, gain, NEG_INF)


def gains_from_histogram(
    hist: jnp.ndarray,
    *,
    total_w: jnp.ndarray,
    attr_is_cont: jnp.ndarray,
    n_bins: jnp.ndarray,
    min_objs: float = 2.0,
    criterion: str = "gain",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-attribute best split score from a ``(..., A, B, C)`` histogram.

    This is the shared "splitAtt" (paper Fig. 3) evaluated for all attributes
    at once.  ``total_w`` broadcasts over the attribute axis; ``attr_is_cont``
    and ``n_bins`` are ``(A,)``.

    Returns ``(score, split_bin)`` of shape ``(..., A)``; ``split_bin`` is the
    threshold bin for continuous attributes and -1 for discrete ones.
    """
    tw = jnp.asarray(total_w)[..., None]                      # broadcast to A
    cont_score, cont_bin = gains_for_continuous(
        hist, total_w=tw, n_bins=n_bins, min_objs=min_objs, criterion=criterion)
    disc_score = gains_for_discrete(
        hist, total_w=tw, n_bins=n_bins, min_objs=min_objs, criterion=criterion)
    attr_is_cont = jnp.asarray(attr_is_cont, dtype=bool)
    score = jnp.where(attr_is_cont, cont_score, disc_score)
    split_bin = jnp.where(attr_is_cont, cont_bin, jnp.int32(-1))
    return score, split_bin


def pick_best_attribute(
    score: jnp.ndarray,
    active: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """splitPost argmax (paper §3.12): first attribute with the maximal score.

    Args:
      score: ``(..., A)`` per-attribute scores (-inf = invalid).
      active: ``(..., A)`` bool — attribute still active at the node (discrete
        attributes used by an ancestor are inactive, paper §2.6).

    Returns:
      ``(best_attr, best_score, has_split)`` — ``has_split`` requires a
      strictly positive score (no-gain nodes become leaves).
    """
    masked = jnp.where(active, score, NEG_INF)
    best_attr = jnp.argmax(masked, axis=-1).astype(jnp.int32)
    best_score = jnp.max(masked, axis=-1)
    has_split = best_score > EPS_GAIN
    return best_attr, best_score, has_split
