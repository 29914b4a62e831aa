"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one kernel for a described (not attached) v5e chip and
compiles it with the TPU compiler, so a block shape Mosaic refuses, an op it
cannot lower, or a VMEM plan over the limit fails here instead of on the
chip.  Nothing runs: results and speed come only from ``chip_smoke.py`` on a
real chip.  Shapes: Table-1 widths at K=256 frontier slots and the tiles
autotune plans for them; the traversal at the smoke test's forest shape.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, histogram, split_gain, tree_infer

N_CASES = 1 << 20
SLOTS = 256
WIDTHS = {                       # (A, B, C)
    "syd10m9a": (9, 256, 2),
    "us_census": (67, 128, 5),
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
