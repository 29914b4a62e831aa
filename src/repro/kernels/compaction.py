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

The ids of the live cases come from :func:`live_case_ids`, a Pallas stream
compaction that uses no scatter.  ``jnp.nonzero`` would lower to a
``bincount`` of the live-case prefix: a scatter with N updates, which a TPU
applies one after another, at about 8 ns a case on every superstep whatever
the bucket.  The kernel instead streams the ``slot`` array once in rows of
128 cases on lanes, in groups of 8 rows:

    rank   = live @ [t' < t]                 (8, 128)  exclusive prefix
    start  = [r' < r] @ row counts           (8, 128)  row offsets in group
    onehot = [pos(t) == p]                   (256, 128) per row
    window = [t; 1] @ onehot^T               (2, 256)   lane index, filled

``rank``, ``start`` and ``window`` are 0/1 or small-integer matmuls on the
MXU, exact in bfloat16 with float32 sums.  A running output offset (the exclusive prefix of the
group counts) lives in SMEM.  A row's 256-position window starts at its
first output position rounded down to 128, and the one-hot absorbs the
remainder; a group's rows land in 16 output rows from an 8-aligned row, so
every store is a whole (8, 128) tile.  Each output position receives
exactly one id, so summing the windows is exact.  The output stays in VMEM,
in passes of at most ``autotune.COMPACT_CHUNK`` ids.  The kernel is called
once per superstep with the largest gather bucket as its length;
``nonzero`` with a smaller size is a prefix of the larger, so each branch
slices it.  It reads the (N,) slots once, builds 256 one-hot entries a
case, and skips its groups once the output is full.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

LANE = autotune.LANE
#: Cases per group: one (8, 128) tile of the slot array.
GROUP = 8 * LANE
_WINDOW = 2 * LANE


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


def _live_ids_kernel(slot_ref, out_ref, off_ref, *, size: int, chunk: int,
                     block_rows: int):
    """Grid (output chunk j, case block i).  Pass j writes the ids of ranks
    ``[j*chunk, min((j+1)*chunk, size))`` into its resident output block."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    chunk_rows = out_ref.shape[0]
    start = j * chunk
    end = jnp.minimum(start + chunk, size)
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        off_ref[0] = 0

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(i32, shape, dim)

    dot = functools.partial(jax.lax.dot, preferred_element_type=f32)
    before_lane = (iota((LANE, LANE), 0) < iota((LANE, LANE), 1)).astype(bf16)
    before_row = (iota((8, 8), 1) < iota((8, 8), 0)).astype(bf16)
    all_lanes = jnp.ones((LANE, LANE), bf16)
    sub, lane = iota((8, LANE), 0), iota((8, LANE), 1)
    # row 0: the case's lane in its row; row 1: 1, to mark a filled position
    lhs = jnp.where(sub == 0, lane, (sub == 1).astype(i32)).astype(bf16)
    window_pos = iota((_WINDOW, LANE), 0)
    out_row = iota((16, LANE), 0)

    def group(g, carry):
        off = off_ref[0]                     # live cases before this group

        @pl.when(off < end)
        def _group():
            live = slot_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :] >= 0
            m = live.astype(f32).astype(bf16)
            rank = dot(m, before_lane).astype(i32)            # (8, T)
            count = dot(m, all_lanes).astype(bf16)             # row's count
            row_start = dot(before_row, count).astype(i32)     # in the group
            total = jnp.sum(live.astype(i32))

            @pl.when((total > 0) & (off + total > start))
            def _place():
                local = off - start
                # 16 output rows from an 8-aligned row take the group's ids
                base = pl.multiple_of(jnp.maximum(local, 0) // GROUP * 8, 8)
                o = local + row_start                          # (8, T)
                pos = o + rank
                keep = live & (pos >= 0) & (pos < end - start)
                p = jnp.where(keep, (o & (LANE - 1)) + rank, -1)
                first = jnp.right_shift(o, 7) - base           # its out row
                case0 = (i * block_rows + g * 8 + sub) * LANE  # its first id
                win = jnp.zeros((16, LANE), i32)
                for r in range(8):
                    onehot = (window_pos == p[r:r + 1]).astype(bf16)
                    got = jax.lax.dot_general(
                        lhs, onehot, (((1,), (1,)), ((), ())),
                        preferred_element_type=f32).astype(i32)   # (8, W)
                    q, c0 = first[r:r + 1], case0[r:r + 1]
                    for h in range(2):
                        ids = (got[0:1, h * LANE:(h + 1) * LANE]
                               + got[1:2, h * LANE:(h + 1) * LANE] * c0)
                        win += jnp.where(out_row == q + h, ids, 0)
                out_ref[pl.ds(base, 8), :] += win[:8]

                @pl.when(base + 8 < chunk_rows)
                def _upper():
                    out_ref[pl.ds(pl.multiple_of(base + 8, 8), 8), :] += (
                        win[8:])

            off_ref[0] = off + total
        return carry

    jax.lax.fori_loop(0, block_rows // 8, group, 0)


@functools.partial(jax.jit,
                   static_argnames=("size", "block_rows", "chunk",
                                    "interpret"))
def live_case_ids(
    slot: jnp.ndarray,       # int32 (N,) frontier slot; -1 = not in frontier
    *,
    size: int,
    block_rows: int,
    chunk: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """int32 (size,): the ascending ids of the cases with ``slot >= 0``,
    padded with 0 -- ``jnp.nonzero(slot >= 0, size=size, fill_value=0)[0]``
    element for element.

    ``block_rows`` rows of 128 cases stream per grid step (a multiple of 8);
    the output is written in passes of ``chunk`` ids (a multiple of
    :data:`GROUP`), each resident in VMEM while the cases stream by.
    """
    if block_rows % 8 or chunk % GROUP:
        raise ValueError(f"block_rows {block_rows} must be a multiple of 8 "
                         f"and chunk {chunk} of {GROUP}")
    n = slot.shape[0]
    step = block_rows * LANE
    n_pad = autotune.round_up(n, step)
    rows = jnp.pad(slot.astype(jnp.int32), (0, n_pad - n),
                   constant_values=-1).reshape(n_pad // LANE, LANE)
    passes = -(-size // chunk)
    out = pl.pallas_call(
        functools.partial(_live_ids_kernel, size=size, chunk=chunk,
                          block_rows=block_rows),
        grid=(passes, n_pad // step),
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((chunk // LANE, LANE), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((passes * chunk // LANE, LANE),
                                       jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=autotune.VMEM_LIMIT),
        interpret=interpret,
        name="live_case_ids",
    )(rows)
    return out.reshape(-1)[:size]


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

    The live-case count, the :func:`live_case_ids` kernel and the gathers
    run under ``jax.named_scope(scope)``, a name the caller may give them in
    its own terms; the histogram kernel keeps its own name.
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
        n_active = jnp.sum((slot >= 0).astype(jnp.int32))
        ids = kernel_ops.live_case_ids(slot, size=sizes[-2])

    def gathered(size: int):
        def run(_):
            with jax.named_scope(scope):
                idx = ids[:size]
                live = jnp.arange(size, dtype=jnp.int32) < n_active
                xg = x[idx]
                sg = jnp.where(live, slot[idx], -1).astype(jnp.int32)
                yg, wg = y[idx], w[idx]
            return kernel_ops.frontier_histogram(xg, yg, wg, sg, **kw)
        return run

    branches = [gathered(s) for s in sizes[:-1]] + [full]
    return jax.lax.switch(pick_bucket(n_active, sizes), branches, None)
