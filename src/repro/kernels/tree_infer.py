"""Pallas TPU kernel: batched forest traversal (level-synchronous descent).

Inference over a packed :class:`~repro.infer.forest.Forest` is the serving
hot-spot: route N cases through T trees of capacity M.  A GPU port would
chase pointers with per-thread gathers; the TPU-native formulation keeps one
tree's node table plus one case block resident in VMEM and turns every
per-depth gather into one-hot MXU matmuls (the same trick as
:mod:`repro.kernels.histogram`).  Cases sit on lanes and nodes on sublanes:

    for each depth step:
        vals = sum over node chunks j of
               tab[j] @ onehot(node over chunk j)      (NODE_COLS, Nblk)
        # vals rows: attr, split_bin, child0, nchild, heavy, class
        Ea   = onehot(attr over A)                     (A, Nblk)
        b    = colsum(Ea * x_block)                    (1, Nblk)  case's bin
        node = route(b, vals)        # continuous / discrete / unknown

The grid is (tree, case block): each kernel instance loads its tree's
``(M/chunk, NODE_COLS, chunk)`` table once and streams ``max_depth`` descent
steps over an ``(A, block_n)`` case tile, emitting the ``block_n`` leaf
classes.  The gathers are exact: every product is a table value times one,
the dots run at ``Precision.HIGHEST`` so the MXU keeps the f32 operand
instead of rounding it to bf16 (node ids above 256 would otherwise come back
wrong), and the table holds integers below 2**24.

Routing semantics match the shared descend step exactly: continuous
attributes test ``b <= split_bin`` (child 0/1), discrete attributes index
the child by bin value, unknown values (``b < 0``) follow the precomputed
heaviest child, and leaves (``nchild == 0``) are absorbing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

#: Row layout of the packed node table (see :meth:`Forest.node_table`).
COL_ATTR, COL_SPLIT, COL_CHILD0, COL_NCHILD, COL_HEAVY, COL_CLASS = range(6)
NODE_COLS = 8          # 6 live rows padded to one 8-sublane tile


def _infer_kernel(tab_ref, x_ref, cont_ref, out_ref, *, max_depth: int):
    n_chunks, _, chunk = tab_ref.shape
    a_dim, n_blk = x_ref.shape
    x = x_ref[...].astype(jnp.float32)          # (A, Nblk) bins; -1 unknown
    cont = cont_ref[...].astype(jnp.float32)        # (A, 1)
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (chunk, n_blk), 0)
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (a_dim, n_blk), 0)

    def gather_rows(node):                          # node (1, Nblk) int32
        def body(j, vals):
            e = (iota_m + j * chunk == node).astype(jnp.float32)
            return vals + jax.lax.dot_general(
                tab_ref[j].astype(jnp.float32), e, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        return jax.lax.fori_loop(
            0, n_chunks, body, jnp.zeros((NODE_COLS, n_blk), jnp.float32))

    def step(_, node):
        vals = gather_rows(node)
        attr = vals[COL_ATTR:COL_ATTR + 1]
        sbin = vals[COL_SPLIT:COL_SPLIT + 1]
        child0 = vals[COL_CHILD0:COL_CHILD0 + 1]
        nchild = vals[COL_NCHILD:COL_NCHILD + 1]
        heavy = vals[COL_HEAVY:COL_HEAVY + 1]
        ea = iota_a == attr.astype(jnp.int32)                 # (A, Nblk)
        b = jnp.sum(jnp.where(ea, x, 0.0), axis=0, keepdims=True)
        is_cont = jnp.sum(jnp.where(ea, cont, 0.0), axis=0,
                          keepdims=True) > 0.5
        child = jnp.where(is_cont, jnp.where(b <= sbin, 0.0, 1.0), b)
        child = jnp.where(b < 0, heavy, child)
        child = jnp.clip(child, 0.0, jnp.maximum(nchild - 1.0, 0.0))
        nxt = (child0 + child).astype(jnp.int32)
        return jnp.where(nchild == 0, node, nxt)

    node = jax.lax.fori_loop(0, max_depth, step,
                             jnp.zeros((1, n_blk), jnp.int32))
    out_ref[...] = gather_rows(node)[COL_CLASS:COL_CLASS + 1].astype(
        jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "block_n", "chunk", "interpret"))
def forest_predict(
    node_tab: jnp.ndarray,       # int32 (T, NODE_COLS, M) packed node table
    x_bins: jnp.ndarray,         # int32 (N, A) bins; -1 = unknown
    attr_is_cont: jnp.ndarray,   # bool (A,)
    *,
    max_depth: int,
    block_n: int,
    chunk: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (T, N) int32 leaf classes, one row per packed tree.

    ``block_n`` is a lane tile (a multiple of 128, or at least N) and
    ``chunk`` (a multiple of 128) the node-table slice of each gather.
    """
    t_dim, cols, m_dim = node_tab.shape
    if cols != NODE_COLS:
        raise ValueError(f"node_tab dim 1 is {cols}, not {NODE_COLS}")
    n, a_dim = x_bins.shape
    block_n = autotune.lane_block(block_n, n)
    np_dim = autotune.round_up(n, block_n)
    mp = autotune.round_up(m_dim, chunk)
    # padding nodes are leaf-shaped (nchild 0) and never reached
    tab = jnp.pad(node_tab.astype(jnp.int32),
                  ((0, 0), (0, 0), (0, mp - m_dim)))
    tab = tab.reshape(t_dim, NODE_COLS, mp // chunk, chunk).transpose(
        0, 2, 1, 3)                                  # (T, M/chunk, 8, chunk)
    xt = jnp.pad(x_bins.astype(jnp.int32).T, ((0, 0), (0, np_dim - n)),
                 constant_values=-1)

    out = pl.pallas_call(
        functools.partial(_infer_kernel, max_depth=max_depth),
        grid=(t_dim, np_dim // block_n),
        in_specs=[
            pl.BlockSpec((None, mp // chunk, NODE_COLS, chunk),
                         lambda t, nb: (t, 0, 0, 0)),
            pl.BlockSpec((a_dim, block_n), lambda t, nb: (0, nb)),
            pl.BlockSpec((a_dim, 1), lambda t, nb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_n), lambda t, nb: (t, 0, nb)),
        out_shape=jax.ShapeDtypeStruct((t_dim, 1, np_dim), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=autotune.VMEM_LIMIT),
        interpret=interpret,
        name="forest_predict",
    )(tab, xt, attr_is_cont.astype(jnp.int32)[:, None])
    return out[:, 0, :n]
