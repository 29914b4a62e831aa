"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first jax
init and only then calls this.
"""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (the sharding rules in
    :mod:`repro.sharding` leave placement to the partitioner)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """v5e pod grid: (data=16, model=16) per pod; 'pod' axis across pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4) -> jax.sharding.Mesh:
    """Small mesh over host devices (tests; needs device_count >= data*model)."""
    return make_auto_mesh((data, model), ("data", "model"))
