"""Shared configuration for the tree-growing engines."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class GrowConfig:
    """Parameters of the C4.5 growth phase (paper Sect. 3.1).

    Attributes:
      min_objs: C4.5 MINOBJS — a node needs weight >= 2*min_objs to split and
        each side of a continuous split needs weight >= min_objs.
      criterion: "gain" (paper footnote 3) or "gain_ratio" (full C4.5).
      max_depth: safety bound on tree depth.
      max_nodes: tree array capacity (frontier engine; oracle grows freely).
      frontier_slots: K — max nodes processed per superstep by the frontier
        engine (the batched analogue of the farm's in-flight task window).
      unknown_fractional: True = full C4.5 semantics, unknown-valued cases go
        to every child with rebalanced weights (sequential oracle only);
        False = route unknowns to the heaviest child (fixed-shape SPMD rule,
        see DESIGN.md §2).
      cost_model: buildAttTest variant for NP/NAP switching: "nsq" (|T|<c·r²,
        paper's best), "nlogn" (|T|<c·r·log r), "alpha" (α<r).
      alpha: the α of the "alpha" cost model (paper uses 1000).
      strategy: "np" (nodes parallelism) or "nap" (nodes+attributes).
      compact: ``impl="pallas"`` only — gather live cases into bucketed
        dense buffers before the histogram kernel, so deep supersteps cost
        O(live) instead of O(N) (see repro.kernels.compaction).
      compact_min_bucket: smallest gather bucket of the power-of-two ladder
        (below this the gather overhead beats the kernel-traffic saving).
      block_t/block_k: pinned Pallas lane tiles (multiples of 128, or the
        whole axis) for the histogram's case axis (t) and the slot axis (k)
        of the histogram and split-gain kernels; None = shape-driven
        heuristic (repro.kernels.autotune).
    """

    min_objs: float = 2.0
    criterion: str = "gain"
    max_depth: int = 64
    max_nodes: int = 1 << 15
    frontier_slots: int = 256
    unknown_fractional: bool = False
    cost_model: str = "nsq"
    alpha: float = 1000.0
    strategy: str = "nap"
    compact: bool = True
    compact_min_bucket: int = 1024
    block_t: int | None = None
    block_k: int | None = None
