"""Active-case compaction for the frontier histogram kernel.

Deep in the build, the open frontier covers a tiny fraction of the training
set, but the histogram kernel's case-tile grid always streams all N cases
through HBM — O(N) traffic per superstep to count a handful of rows.  This
module gathers the cases whose node is in the open frontier into a dense
``(N_active,)`` buffer before the kernel runs, so the case-tile grid scales
with *live* cases.

Shapes must stay static under jit (the build is a ``lax.while_loop``), so
the gather size comes from a small ladder of power-of-two *buckets*: the
live count selects the smallest bucket that fits via ``lax.switch``, and
each branch traces the kernel at its own static size.  The largest bucket
is N itself and skips the gather entirely (no regression on shallow
supersteps where everything is live).

Per-superstep cost: one ``nonzero`` scan + gather (O(N) but elementwise,
~16 B/case) replaces O(N * A * ceil(K/block_k)) kernel traffic — a win
whenever the frontier is sparse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bucket_sizes(n_cases: int, *, min_bucket: int = 1024) -> tuple[int, ...]:
    """Static gather-size ladder: powers of two from ``min_bucket`` to N.

    The final bucket is exactly ``n_cases`` (the no-gather fallback).  A
    single-element ladder means compaction is a no-op for small problems —
    callers can skip the switch entirely.
    """
    n_cases = int(n_cases)
    min_bucket = max(8, int(min_bucket))
    if n_cases <= min_bucket:
        return (n_cases,)
    sizes = []
    b = min_bucket
    while b < n_cases:
        sizes.append(b)
        b <<= 1
    sizes.append(n_cases)
    return tuple(sizes)


def pick_bucket(n_active: jnp.ndarray, sizes: tuple[int, ...]) -> jnp.ndarray:
    """Index into ``sizes`` of the smallest bucket that holds ``n_active``
    live cases: the gather branch the histogram takes, and so the number of
    cases its kernel is given (``sizes[index]``)."""
    return jnp.searchsorted(jnp.asarray(sizes, jnp.int32), n_active,
                            side="left").astype(jnp.int32)


def compact_frontier_histogram(
    x: jnp.ndarray,          # int32 (N, A) bins; -1 = unknown
    y: jnp.ndarray,          # int32 (N,) class labels
    w: jnp.ndarray,          # f32 (N,) case weights
    slot: jnp.ndarray,       # int32 (N,) frontier slot; -1 = not in frontier
    *,
    n_slots: int,
    n_bins: int,
    n_classes: int,
    min_bucket: int = 1024,
    block_t: int | None = None,
    block_k: int | None = None,
    scope: str = "compaction",
) -> jnp.ndarray:
    """(K, A, B+1, C) weighted counts over the compacted live cases.

    The live-case count, the ``nonzero`` and the gathers run under
    ``jax.named_scope(scope)``, a name the caller may give them in its own
    terms; the kernel keeps its own name.
    """
    from repro.kernels import ops as kernel_ops

    x = jnp.asarray(x)
    y = jnp.asarray(y)
    w = jnp.asarray(w)
    slot = jnp.asarray(slot)
    n = x.shape[0]
    kw = dict(n_slots=n_slots, n_bins=n_bins, n_classes=n_classes,
              block_t=block_t, block_k=block_k)

    def full(_):
        return kernel_ops.frontier_histogram(x, y, w, slot, **kw)

    sizes = bucket_sizes(n, min_bucket=min_bucket)
    if len(sizes) == 1:
        return full(None)

    with jax.named_scope(scope):
        part = slot >= 0
        n_active = jnp.sum(part.astype(jnp.int32))

    def gathered(size: int):
        def run(_):
            with jax.named_scope(scope):
                idx = jnp.nonzero(part, size=size, fill_value=0)[0]
                live = jnp.arange(size, dtype=jnp.int32) < n_active
                xg = x[idx]
                sg = jnp.where(live, slot[idx], -1).astype(jnp.int32)
                yg, wg = y[idx], w[idx]
            return kernel_ops.frontier_histogram(xg, yg, wg, sg, **kw)
        return run

    branches = [gathered(s) for s in sizes[:-1]] + [full]
    return jax.lax.switch(pick_bucket(n_active, sizes), branches, None)
