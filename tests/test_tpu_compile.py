"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one kernel for a described (not attached) v5e chip and
compiles it with the TPU compiler, so a block shape Mosaic refuses, an op it
cannot lower, or a VMEM plan over the limit fails here instead of on the
chip.  Nothing runs: results and speed come only from ``chip_smoke.py`` on a
real chip.  Shapes: Table-1 widths at K=256 frontier slots and the tiles
autotune plans for them; the traversal at the smoke test's forest shape.
The frontier's case routing and splitPre are compiled too, to show that the
chip's compiler leaves neither with a gather over the cases.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import frontier
from repro.core.config import GrowConfig
from repro.kernels import (autotune, compaction, histogram, split_gain,
                           tree_infer)

N_CASES = 1 << 20
SLOTS = 256
WIDTHS = {                       # (A, B, C)
    "syd10m9a": (9, 256, 2),
    "us_census": (67, 11, 5),    # the stand-in's widest attribute: 11
    "kddcup99": (41, 256, 23),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    # A persistent cache cannot read these entries back without a chip and
    # warns on every try, so keep it off while this module compiles.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    yield make
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _assert_mosaic(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_histogram_compiles_for_v5e(spec, width):
    a, b, c = WIDTHS[width]
    plan = autotune.plan_blocks(n_cases=N_CASES, n_slots=SLOTS, n_bins=b,
                                n_classes=c)
    autotune.check_vmem(autotune.hist_vmem_bytes(
        block_t=plan.block_t, block_k=plan.block_k, n_bins=b, n_classes=c),
        width)
    compiled = histogram.frontier_histogram.lower(
        spec((N_CASES, a), jnp.int32), spec((N_CASES,), jnp.int32),
        spec((N_CASES,), jnp.float32), spec((N_CASES,), jnp.int32),
        n_slots=SLOTS, n_bins=b, n_classes=c, block_t=plan.block_t,
        block_k=plan.block_k).compile()
    _assert_mosaic(compiled, "frontier_histogram")


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_split_gain_compiles_for_v5e(spec, width):
    a, b, c = WIDTHS[width]
    plan = autotune.plan_blocks(n_cases=N_CASES, n_slots=SLOTS, n_bins=b,
                                n_classes=c)
    autotune.check_vmem(autotune.gain_vmem_bytes(
        block_k=plan.block_k, n_bins=b, n_classes=c), width)
    compiled = split_gain.split_gain.lower(
        spec((SLOTS, a, b, c), jnp.float32), spec((SLOTS,), jnp.float32),
        spec((a,), jnp.bool_), spec((a,), jnp.int32),
        criterion="gain_ratio", block_k=plan.block_k).compile()
    _assert_mosaic(compiled, "split_gain")


@pytest.mark.parametrize("n_cases", [N_CASES, 4_000_000])
def test_live_case_ids_compiles_for_v5e(spec, n_cases):
    # the largest gather bucket of the ladder; at 4M cases its 2**21 ids
    # come out in two passes of the kernel
    size = compaction.bucket_sizes(n_cases)[-2]
    plan = autotune.plan_compact(n_cases=n_cases, size=size)
    autotune.check_vmem(autotune.compact_vmem_bytes(
        block_rows=plan.block_rows, chunk=plan.chunk), "live_case_ids")
    compiled = compaction.live_case_ids.lower(
        spec((n_cases,), jnp.int32), size=size, block_rows=plan.block_rows,
        chunk=plan.chunk).compile()
    _assert_mosaic(compiled, "live_case_ids")


def test_traversal_compiles_for_v5e(spec):
    # the smoke test's serving forest on the chip: 8 trees grown on 50k
    # SyD10M9A cases (capacity 16,901, 54 levels), a 128-row microbatch
    trees, capacity, rows, attrs, levels = 8, 16_901, 128, 9, 54
    plan = autotune.plan_infer_blocks(n_cases=rows, capacity=capacity,
                                      n_attrs=attrs)
    autotune.check_vmem(autotune.infer_vmem_bytes(
        block_n=plan.block_n, capacity=capacity, chunk=plan.chunk,
        n_attrs=attrs), "traversal")
    compiled = tree_infer.forest_predict.lower(
        spec((trees, tree_infer.NODE_COLS, capacity), jnp.int32),
        spec((rows, attrs), jnp.int32), spec((attrs,), jnp.bool_),
        max_depth=levels, block_n=plan.block_n, chunk=plan.chunk).compile()
    _assert_mosaic(compiled, "forest_predict")


def test_route_compiles_for_v5e_without_a_gather(spec):
    a = WIDTHS["syd10m9a"][0]
    route = jax.jit(lambda case_node, slot, x, attr, thr, heaviest, child0:
                    frontier.route(case_node, slot, x, attr=attr, thr=thr,
                                   heaviest=heaviest, child0=child0))
    per_case = spec((N_CASES,), jnp.int32)
    per_slot = spec((SLOTS,), jnp.int32)
    compiled = route.lower(per_case, per_case, spec((N_CASES, a), jnp.int32),
                           per_slot, per_slot, per_slot, per_slot).compile()
    assert " gather(" not in compiled.as_text()


_GATHER = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[(\d+)[\],].*? gather\(",
                     re.MULTILINE)


def test_split_pre_compiles_for_v5e_without_a_gather_over_the_cases(spec):
    # splitPre selects the open id range and gives each case its slot by
    # subtraction: no gather may have a result over the N cases.
    a, b, c = WIDTHS["syd10m9a"]
    prob = frontier.FrontierProblem(
        n_cases=N_CASES, n_attrs=a, n_bins_max=b, n_classes=c,
        max_children=20, cfg=GrowConfig(max_nodes=1 << 18,
                                        frontier_slots=SLOTS))
    state = jax.eval_shape(lambda: frontier.init_state(
        prob, jnp.zeros((N_CASES,), jnp.int32),
        jnp.ones((N_CASES,), jnp.float32)))
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    compiled = jax.jit(functools.partial(frontier.split_pre, prob=prob)
                       ).lower(state).compile()
    assert [d for d in _GATHER.findall(compiled.as_text())
            if int(d) == N_CASES] == []
