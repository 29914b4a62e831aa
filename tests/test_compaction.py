"""Active-case compaction + block autotune: invariants and equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, compaction, ops, ref


def test_bucket_ladder_shape():
    assert compaction.bucket_sizes(500, min_bucket=1024) == (500,)
    assert compaction.bucket_sizes(1024, min_bucket=1024) == (1024,)
    assert compaction.bucket_sizes(3000, min_bucket=1024) == (1024, 2048, 3000)
    assert compaction.bucket_sizes(4096, min_bucket=512) == (512, 1024, 2048,
                                                             4096)
    ladder = compaction.bucket_sizes(100_000, min_bucket=1024)
    assert ladder[-1] == 100_000 and all(
        b == 1024 << i for i, b in enumerate(ladder[:-1]))


def _problem(rng, n, a, b, c, k, frac_active):
    x = rng.integers(-1, b, (n, a)).astype(np.int32)
    y = rng.integers(0, c, n).astype(np.int32)
    w = rng.uniform(0.1, 2.0, n).astype(np.float32)
    slot = rng.integers(0, k, n).astype(np.int32)
    slot[rng.random(n) >= frac_active] = -1
    return x, y, w, slot


@pytest.mark.parametrize("frac_active", [0.0, 0.03, 0.5, 1.0])
def test_compact_matches_full(frac_active):
    """Bucketed gather == full-N kernel == jnp reference, any liveness."""
    rng = np.random.default_rng(int(frac_active * 100))
    n, a, b, c, k = 700, 3, 9, 4, 6
    x, y, w, slot = _problem(rng, n, a, b, c, k, frac_active)
    kw = dict(n_slots=k, n_bins=b, n_classes=c)
    want = np.asarray(ref.frontier_histogram_ref(x, y, w, slot, **kw))
    got = np.asarray(ops.frontier_histogram_compact(
        x, y, w, slot, min_bucket=64, **kw))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


def test_compact_under_jit_selects_buckets():
    """The lax.switch ladder is jit-safe and every bucket agrees."""
    rng = np.random.default_rng(3)
    n, a, b, c, k = 512, 2, 5, 3, 4
    kw = dict(n_slots=k, n_bins=b, n_classes=c, min_bucket=32)

    @jax.jit
    def go(x, y, w, slot):
        return ops.frontier_histogram_compact(x, y, w, slot, **kw)

    for n_live in (0, 1, 31, 32, 33, 200, 512):
        x, y, w, slot = _problem(rng, n, a, b, c, k, 1.0)
        slot[n_live:] = -1
        want = np.asarray(ref.frontier_histogram_ref(
            x, y, w, slot, n_slots=k, n_bins=b, n_classes=c))
        np.testing.assert_allclose(np.asarray(go(x, y, w, slot)), want,
                                   atol=1e-4, rtol=1e-5, err_msg=str(n_live))


def test_compact_gather_is_dense():
    """Live rows land contiguously; padding rows carry slot -1 (masked)."""
    slot = jnp.array([-1, 2, -1, 0, -1, -1, 1, -1], jnp.int32)
    part = slot >= 0
    idx = jnp.nonzero(part, size=4, fill_value=0)[0]
    live = jnp.arange(4) < jnp.sum(part.astype(jnp.int32))
    assert np.asarray(idx).tolist() == [1, 3, 6, 0]        # last is filler
    assert np.asarray(
        jnp.where(live, slot[idx], -1)).tolist() == [2, 0, 1, -1]


def _live_slots(case, n):
    """Slots of ``n`` cases for one liveness pattern (-1 = not live)."""
    rng = np.random.default_rng(n)
    live = np.zeros(n, bool)
    if case == "one":
        live[n // 2 + 1] = True
    elif case == "all":
        live[:] = True
    elif case == "last_tile":          # live only in the last 128-case row
        live[-(n % 128 or 128):] = rng.random(n % 128 or 128) < 0.5
    elif case.startswith("count="):    # exactly this many, scattered
        live[rng.choice(n, int(case[6:]), replace=False)] = True
    elif case == "sparse":
        live = rng.random(n) < 0.03
    return np.where(live, rng.integers(0, 7, n), -1).astype(np.int32)


# (case, N, S): N = 3,000 and 2,500 are not multiples of the 128-case row or
# the 1,024-case group; 512 and 1,024 are bucket edges of a 64-case ladder.
LIVE_CASES = [("none", 3000, 2048), ("one", 3000, 2048), ("all", 3000, 2048),
              ("last_tile", 3000, 2048), ("count=512", 3000, 2048),
              ("count=513", 3000, 2048), ("count=1024", 2500, 1024),
              ("count=1025", 2500, 1024), ("sparse", 2500, 1024),
              ("all", 2500, 64), ("count=300", 300, 256)]


@pytest.mark.parametrize("tiles", ["planned", "small"])
@pytest.mark.parametrize("case,n,size", LIVE_CASES,
                         ids=[f"{c}-n{n}-s{s}" for c, n, s in LIVE_CASES])
def test_live_case_ids_equal_nonzero(case, n, size, tiles):
    """The stream-compaction kernel gives ``nonzero``'s ids exactly: live
    ids ascending, then 0.  ``small`` tiles stream 1,024 cases per grid step
    and write the output in passes of 1,024 ids, so live runs cross steps
    and passes."""
    slot = jnp.asarray(_live_slots(case, n))
    want = np.asarray(jnp.nonzero(slot >= 0, size=size, fill_value=0)[0])
    if tiles == "planned":
        got = ops.live_case_ids(slot, size=size)
    else:
        got = compaction.live_case_ids(slot, size=size, block_rows=8,
                                       chunk=compaction.GROUP,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_live_case_ids_refuses_unaligned_tiles():
    slot = jnp.zeros((256,), jnp.int32)
    with pytest.raises(ValueError, match="multiple"):
        compaction.live_case_ids(slot, size=64, block_rows=4, chunk=1024,
                                 interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        compaction.live_case_ids(slot, size=64, block_rows=8, chunk=512,
                                 interpret=True)


def test_compact_plan_keeps_vmem_flat_in_n():
    for n, size in [(300, 256), (500_000, 262_144), (245_828, 131_072),
                    (10_000_000, 8_388_608)]:
        p = autotune.plan_compact(n_cases=n, size=size)
        assert p.block_rows % 8 == 0 and p.chunk % compaction.GROUP == 0
        assert p.block_rows * 128 <= autotune.round_up(n, 1024)
        assert size <= p.chunk or p.chunk == autotune.COMPACT_CHUNK
        assert autotune.compact_vmem_bytes(
            block_rows=p.block_rows, chunk=p.chunk) <= autotune.VMEM_BUDGET


def test_autotune_respects_budget_and_extents():
    for n, k, b, c, a in [(100, 4, 3, 2, 2), (1 << 20, 256, 128, 23, 41),
                          (50_000, 64, 64, 7, 54),
                          (1 << 20, 256, 256, 23, 41)]:
        p = autotune.plan_blocks(n_cases=n, n_slots=k, n_bins=b,
                                 n_classes=c)
        # padded, double-buffered blocks of both kernels fit the budget
        hist_bytes = autotune.hist_vmem_bytes(
            block_t=p.block_t, block_k=p.block_k, n_bins=b, n_classes=c)
        gain_bytes = autotune.gain_vmem_bytes(block_k=p.block_k, n_bins=b,
                                              n_classes=c)
        assert hist_bytes <= autotune.VMEM_BUDGET, (n, k, b, c, a)
        assert gain_bytes <= autotune.VMEM_BUDGET, (n, k, b, c, a)
        # lane tiles: 128-aligned or the whole axis
        assert p.block_t == n or p.block_t % 128 == 0
        assert p.block_k == k or p.block_k % 128 == 0


def test_autotune_counts_padded_double_buffered_vmem():
    # a (1, 1) int32 block still takes one (8, 128) tile
    assert autotune.tile_bytes(1, 1) == 8 * 128 * 4
    assert autotune.tile_bytes(3, 9, 130) == 3 * 16 * 256 * 4
    # an (8, M) node table at M = 2**15 is 1 MiB per buffer
    m = 1 << 15
    small = autotune.infer_vmem_bytes(block_n=128, capacity=128, chunk=128,
                                      n_attrs=9)
    big = autotune.infer_vmem_bytes(block_n=128, capacity=m, chunk=128,
                                    n_attrs=9)
    assert big - small == 2 * 8 * (m - 128) * 4
    with pytest.raises(autotune.VmemError, match="MiB"):
        ops.forest_predict(jnp.zeros((1, 8, 1 << 22), jnp.int32),
                           jnp.zeros((4, 9), jnp.int32),
                           jnp.zeros((9,), bool), max_depth=1)


def test_autotune_overrides_win():
    p = autotune.plan_blocks(n_cases=10_000, n_slots=64, n_bins=32,
                             n_classes=4,
                             block_t=128, block_k=64)
    assert (p.block_t, p.block_k) == (128, 64)


def test_plan_for_config_reads_grow_config():
    from repro.core.config import GrowConfig
    cfg = GrowConfig(frontier_slots=384, block_k=128)
    p = autotune.plan_for_config(cfg, n_cases=5000, n_bins=16, n_classes=3)
    assert p.block_k == 128
    assert p.block_t == 1024        # largest case tile that fits
