"""Pallas TPU kernel: frontier histogram via one-hot MXU matmuls.

The splitAtt hot-spot of the SPMD tree engine is building the
``(K nodes, A attrs, B+1 bins, C classes)`` weighted-count tensor from N
cases.  A GPU port would scatter-add into gmem atomics; the TPU-native
formulation turns the scatter into matmuls so the MXU does the counting.
Cases sit on lanes throughout, and the slot axis of the product fills the
MXU's output columns:

    for each slot block, attribute a and case tile T:
        E   = onehot(bin of a)                      (Bp, T)   Bp = B+1 -> x8
        for each class c:
            S_c = onehot(slot) * w * [y == c]       (Kblk, T)
            hist[a, c] += E @ S_c^T                 (Bp, Kblk)

The grid is (slot blocks, A, case tiles) with the case-tile axis innermost,
so each ``(C, Bp, Kblk)`` output block stays resident in VMEM while the case
tiles stream through.

Exact by construction plus HIGHEST precision: ``E`` is 0/1 and ``S_c`` holds
one weight per column, so every product is a weight times one.  The dots run
at ``Precision.HIGHEST`` so the MXU does not round the weights to bf16, and
accumulate in f32, so sums of integer weights stay exact below 2**24.

Unknown values occupy the extra bin index B (consumed by splitPost for the
heaviest-child routing).  Cases whose node is not in the frontier carry
slot = -1 and fall outside every slot block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune


def _hist_kernel(x_ref, key_ref, w_ref, out_ref, *, block_k: int,
                 n_classes: int, rows: int):
    kb = pl.program_id(0)
    a = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = x_ref.shape[-1]
    xb = x_ref[pl.ds(a % rows, 1), :]                 # (1, T) bin of attr a
    key = key_ref[...] - kb * (block_k * n_classes)   # (1, T) slot*C + class
    wv = w_ref[...]                                   # (1, T)
    bp = out_ref.shape[1]
    e = (jax.lax.broadcasted_iota(jnp.int32, (bp, tile), 0) == xb
         ).astype(jnp.float32)                         # (Bp, T)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_k, tile), 0) * n_classes

    def per_class(c, carry):
        s = jnp.where(cols + c == key, wv, 0.0)      # (Kblk, T)
        out_ref[c] += jax.lax.dot_general(
            e, s, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # (Bp, Kblk)
        return carry

    jax.lax.fori_loop(0, n_classes, per_class, 0)


@functools.partial(
    jax.jit,
    static_argnames=("n_slots", "n_bins", "n_classes", "block_t", "block_k",
                     "interpret"))
def frontier_histogram(
    x: jnp.ndarray,          # int32 (N, A) bins; -1 = unknown
    y: jnp.ndarray,          # int32 (N,) class labels
    w: jnp.ndarray,          # f32 (N,) case weights
    slot: jnp.ndarray,       # int32 (N,) frontier slot; -1 = not in frontier
    *,
    n_slots: int,
    n_bins: int,             # B; the kernel emits B+1 (unknown bin last)
    n_classes: int,
    block_t: int,
    block_k: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (K, A, B+1, C) float32 weighted counts.

    ``block_t`` and ``block_k`` must be lane tiles: multiples of 128, or at
    least the padded extent (then the whole axis is one block).
    """
    n, a_dim = x.shape
    c_dim = n_classes
    b1 = n_bins + 1
    bp = autotune.round_up(b1, 8)
    block_t = autotune.lane_block(block_t, n)
    block_k = autotune.lane_block(block_k, n_slots)
    np_dim = autotune.round_up(n, block_t)
    kp = autotune.round_up(n_slots, block_k)
    # x goes in attribute-major with cases on lanes; the kernel DMAs 8
    # attribute rows per step (or all of them when A < 8) and picks its row.
    rows = min(8, a_dim)
    ap = autotune.round_up(a_dim, rows)

    # Unknown values -> bin index B.  One int32 key folds slot and class, so
    # padding cases (slot -1) and out-of-frontier cases never match a column.
    x = jnp.where(x >= 0, x, n_bins).astype(jnp.int32)
    key = jnp.where(slot >= 0, slot * c_dim + y, -1).astype(jnp.int32)
    pad_n = np_dim - n
    xt = jnp.pad(x.T, ((0, ap - a_dim), (0, pad_n)))
    key = jnp.pad(key, (0, pad_n), constant_values=-1)[None, :]
    w = jnp.pad(w.astype(jnp.float32), (0, pad_n))[None, :]

    grid = (kp // block_k, a_dim, np_dim // block_t)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, block_k=block_k, n_classes=c_dim,
                          rows=rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block_t), lambda kb, a, t: (a // rows, t)),
            pl.BlockSpec((1, block_t), lambda kb, a, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda kb, a, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((None, c_dim, bp, block_k),
                               lambda kb, a, t: (a, 0, 0, kb)),
        out_shape=jax.ShapeDtypeStruct((a_dim, c_dim, bp, kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=autotune.VMEM_LIMIT),
        interpret=interpret,
        name="frontier_histogram",
    )(xt, key, w)
    # (A, C, Bp, Kp) -> (K, A, B+1, C)
    return out[:, :, :b1, :n_slots].transpose(3, 0, 2, 1)
