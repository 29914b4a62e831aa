"""Public wrappers over the Pallas kernels.

On a TPU the kernels are compiled by Mosaic.  On the ``cpu`` backend, which
the tests run on, they execute in ``interpret=True`` mode, so every caller
-- the frontier engine with ``impl="pallas"`` included -- exercises the real
kernel bodies.  No other backend and no other fallback exists: a shape whose
blocks cannot fit VMEM raises :class:`~repro.kernels.autotune.VmemError`
here, before anything is traced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import histogram as _histogram
from repro.kernels import split_gain as _split_gain
from repro.kernels import tree_infer as _tree_infer


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def frontier_histogram(x, y, w, slot, *, n_slots: int, n_bins: int,
                       n_classes: int, block_t: int | None = None,
                       block_k: int | None = None) -> jnp.ndarray:
    """(K, A, B+1, C) weighted counts -- MXU one-hot matmul kernel."""
    n, a_dim = x.shape
    plan = autotune.plan_blocks(
        n_cases=n, n_slots=n_slots, n_bins=n_bins, n_classes=n_classes,
        block_t=block_t, block_k=block_k)
    block_t = autotune.lane_block(plan.block_t, n)
    block_k = autotune.lane_block(plan.block_k, n_slots)
    autotune.check_vmem(autotune.hist_vmem_bytes(
        block_t=block_t, block_k=block_k, n_bins=n_bins,
        n_classes=n_classes), "frontier_histogram")
    return _histogram.frontier_histogram(
        x, y, w, slot, n_slots=n_slots, n_bins=n_bins, n_classes=n_classes,
        block_t=block_t, block_k=block_k, interpret=_interpret())


def frontier_histogram_compact(x, y, w, slot, *, n_slots: int, n_bins: int,
                               n_classes: int, min_bucket: int = 1024,
                               block_t: int | None = None,
                               block_k: int | None = None) -> jnp.ndarray:
    """Histogram kernel over the compacted live cases (bucketed gather).

    Same contract as :func:`frontier_histogram`; the case-tile grid scales
    with the open frontier's live-case count instead of N (see
    :mod:`repro.kernels.compaction`).
    """
    from repro.kernels import compaction
    return compaction.compact_frontier_histogram(
        x, y, w, slot, n_slots=n_slots, n_bins=n_bins, n_classes=n_classes,
        min_bucket=min_bucket, block_t=block_t, block_k=block_k)


def forest_predict(node_tab, x_bins, attr_is_cont, *, max_depth: int,
                   block_n: int | None = None):
    """(T, N) leaf classes -- level-synchronous MXU traversal kernel."""
    _, _, m_dim = node_tab.shape
    n, a_dim = x_bins.shape
    plan = autotune.plan_infer_blocks(
        n_cases=n, capacity=m_dim, n_attrs=a_dim, block_n=block_n)
    block_n = autotune.lane_block(plan.block_n, n)
    autotune.check_vmem(autotune.infer_vmem_bytes(
        block_n=block_n, capacity=m_dim, chunk=plan.chunk, n_attrs=a_dim),
        "forest_predict")
    return _tree_infer.forest_predict(
        node_tab, x_bins, attr_is_cont, max_depth=max_depth,
        block_n=block_n, chunk=plan.chunk, interpret=_interpret())


def split_gain(hist, total_w, attr_is_cont, n_bins, *, min_objs: float = 2.0,
               criterion: str = "gain", block_k: int | None = None):
    """(score, split_bin) per (node, attribute): fused scan/entropy kernel."""
    k, a_dim, b_dim, c_dim = hist.shape
    if block_k is None:
        block_k = autotune.plan_blocks(
            n_cases=1, n_slots=k, n_bins=b_dim, n_classes=c_dim).block_k
    block_k = autotune.lane_block(block_k, k)
    autotune.check_vmem(autotune.gain_vmem_bytes(
        block_k=block_k, n_bins=b_dim, n_classes=c_dim), "split_gain")
    return _split_gain.split_gain(
        hist, total_w, attr_is_cont, n_bins, min_objs=min_objs,
        criterion=criterion, block_k=block_k, interpret=_interpret())
