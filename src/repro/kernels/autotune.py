"""Block-size planning and VMEM accounting for the Pallas kernels.

Every kernel is compiled under one scoped-VMEM limit, :data:`VMEM_LIMIT`,
passed explicitly through ``pltpu.CompilerParams(vmem_limit_bytes=...)``.
The planners count the bytes a block really takes in VMEM: the last dim is
padded to 128 lanes, the second-to-last to 8 sublanes, pipelined inputs and
outputs are double-buffered, and the large in-kernel temporaries are added
on top.  A plan that cannot fit raises :class:`VmemError`; nothing falls back
to another path.

Tiles on a lane axis are multiples of 128 or the whole (padded) axis, the
rule Mosaic enforces for the last two block dims.  ``block_t`` and
``block_k`` can be pinned through :class:`repro.core.config.GrowConfig`;
``None`` means "use the heuristic".
"""

from __future__ import annotations

import dataclasses

LANE, SUBLANE = 128, 8

# Scoped VMEM each kernel compiles under (a TPU v5e core has 128 MiB).
VMEM_LIMIT = 32 << 20
# Share of the limit the planners may fill; Mosaic keeps the rest for its
# own scratch (matmul staging, relayouts).
VMEM_BUDGET = VMEM_LIMIT * 3 // 4


class VmemError(ValueError):
    """A kernel's blocks cannot fit :data:`VMEM_BUDGET` at any legal tile."""


def round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def lane_block(block: int, extent: int) -> int:
    """A legal lane tile for an axis of ``extent``: the whole axis when
    ``block`` covers it, else ``block`` itself, which must be 128-aligned."""
    if block >= extent:
        return extent
    if block % LANE:
        raise ValueError(f"lane tile {block} must be a multiple of {LANE} "
                         f"or cover the whole axis ({extent})")
    return block


def tile_bytes(*shape: int, itemsize: int = 4) -> int:
    """VMEM bytes of a 32-bit array block: lanes to 128, sublanes to 8."""
    *lead, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = round_up(sub, SUBLANE) * round_up(lane, LANE) * itemsize
    for d in lead:
        n *= d
    return n


def hist_vmem_bytes(*, block_t: int, block_k: int, n_bins: int,
                    n_classes: int) -> int:
    bp = round_up(n_bins + 1, SUBLANE)
    io = (3 * tile_bytes(SUBLANE, block_t)                # x rows, key, w
          + tile_bytes(n_classes, bp, block_k))           # out window
    temps = (tile_bytes(bp, block_t)                      # E
             + 3 * tile_bytes(block_k, block_t)           # iota, S_c, mask
             + tile_bytes(bp, block_k))                   # E @ S_c^T
    return 2 * io + temps


def gain_vmem_bytes(*, block_k: int, n_bins: int, n_classes: int) -> int:
    bg = round_up(n_bins, SUBLANE)
    io = tile_bytes(n_classes, bg, block_k) + 3 * tile_bytes(1, block_k)
    temps = tile_bytes(bg, bg) + 12 * tile_bytes(bg, block_k)
    return 2 * io + temps


def infer_vmem_bytes(*, block_n: int, capacity: int, chunk: int,
                     n_attrs: int, node_cols: int = 8) -> int:
    io = (tile_bytes(round_up(capacity, chunk) // chunk, node_cols, chunk)
          + tile_bytes(n_attrs, block_n) + tile_bytes(n_attrs, 1)
          + tile_bytes(1, block_n))
    temps = 3 * tile_bytes(chunk, block_n) + 4 * tile_bytes(n_attrs, block_n)
    return 2 * io + temps


def compact_vmem_bytes(*, block_rows: int, chunk: int) -> int:
    io = tile_bytes(block_rows, LANE) + tile_bytes(chunk // LANE, LANE)
    temps = (3 * tile_bytes(2 * LANE, LANE)               # window iota, one-hot
             + 3 * tile_bytes(LANE, LANE)                 # prefix matrices
             + 12 * tile_bytes(16, LANE))                 # (8|16, T) values
    return 2 * io + temps


def check_vmem(nbytes: int, what: str) -> None:
    if nbytes > VMEM_BUDGET:
        raise VmemError(
            f"{what}: blocks need {nbytes / 2**20:.1f} MiB of VMEM, over the "
            f"{VMEM_BUDGET / 2**20:.1f} MiB budget of the {VMEM_LIMIT >> 20} "
            "MiB limit; shrink the tiles or the problem")


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Static tile sizes for one frontier problem shape.

    ``block_t`` tiles the histogram kernel's case axis; ``block_k`` tiles
    the slot axis of both the histogram and the split-gain kernel.
    """
    block_t: int
    block_k: int


def plan_blocks(
    *,
    n_cases: int,
    n_slots: int,
    n_bins: int,          # B: the histogram kernel emits B+1 (unknown bin)
    n_classes: int,
    block_t: int | None = None,
    block_k: int | None = None,
) -> BlockPlan:
    """Choose tile sizes from the problem shape (overrides win).

    The slot tile is the whole slot axis up to 256, else the largest
    128-multiple both kernels fit; the case tile is the whole case axis up
    to 1024 (small problems: one step), else the largest 128-multiple up to
    1024 that fits beside it.
    """
    c = max(1, n_classes)
    if block_k is None:
        ks = [n_slots] if n_slots <= 2 * LANE else [2 * LANE, LANE]
        ks = [k for k in ks
              if gain_vmem_bytes(block_k=k, n_bins=n_bins, n_classes=c)
              <= VMEM_BUDGET] or ks[-1:]
        block_k = ks[0]
    if block_t is None:
        ts = [n_cases] if n_cases <= 1024 else [1024, 512, 256, LANE]
        ts = [t for t in ts
              if hist_vmem_bytes(block_t=t, block_k=block_k, n_bins=n_bins,
                                 n_classes=c) <= VMEM_BUDGET] or ts[-1:]
        block_t = ts[0]
    return BlockPlan(block_t=block_t, block_k=block_k)


#: Rows of 128 cases the compaction kernel streams per grid step, and the
#: most ids one pass of it keeps in VMEM (4 MiB of int32).
COMPACT_ROWS = 64
COMPACT_CHUNK = 1 << 20


@dataclasses.dataclass(frozen=True)
class CompactPlan:
    """Static tiles of :func:`repro.kernels.compaction.live_case_ids`: case
    rows per grid step, and ids per output pass (a multiple of 8 rows)."""
    block_rows: int
    chunk: int


def plan_compact(*, n_cases: int, size: int) -> CompactPlan:
    """The case rows stream :data:`COMPACT_ROWS` at a time (all of them for
    a smaller problem); the ids come out in passes of at most
    :data:`COMPACT_CHUNK`, so VMEM does not grow with N."""
    rows = round_up(max(1, -(-int(n_cases) // LANE)), SUBLANE)
    return CompactPlan(
        block_rows=min(COMPACT_ROWS, rows),
        chunk=min(COMPACT_CHUNK, round_up(size, SUBLANE * LANE)))


@dataclasses.dataclass(frozen=True)
class InferBlockPlan:
    """Static tiles of the forest-traversal kernel: the case tile and the
    node-table chunk each one-hot gather covers."""
    block_n: int
    chunk: int


def plan_infer_blocks(
    *,
    n_cases: int,
    capacity: int,          # M: padded node count per packed tree
    n_attrs: int,
    block_n: int | None = None,
) -> InferBlockPlan:
    """Tiles for :mod:`repro.kernels.tree_infer` (override wins).

    The node table stays resident per tree; each descent step gathers from
    it in ``chunk``-node slices with a ``(chunk, block_n)`` one-hot, so VMEM
    grows with M only through the table itself.
    """
    chunk = min(512, round_up(capacity, LANE))
    if block_n is None:
        ns = [n_cases] if n_cases <= 2 * LANE else [512, 2 * LANE, LANE]
        ns = [n for n in ns
              if infer_vmem_bytes(block_n=n, capacity=capacity, chunk=chunk,
                                  n_attrs=n_attrs) <= VMEM_BUDGET] or ns[-1:]
        block_n = ns[0]
    return InferBlockPlan(block_n=block_n, chunk=chunk)


def plan_for_config(cfg, *, n_cases: int, n_bins: int,
                    n_classes: int) -> BlockPlan:
    """Plan from a :class:`GrowConfig` (its ``block_*`` fields pin tiles)."""
    return plan_blocks(
        n_cases=n_cases, n_slots=cfg.frontier_slots, n_bins=n_bins,
        n_classes=n_classes, block_t=cfg.block_t, block_k=cfg.block_k)
