"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax init; a
module-level mesh would lock the device count prematurely).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods x 256
    chips as (pod=2, data=16, model=16) — the 'pod' axis extends data
    parallelism across the inter-pod (DCN-class) links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_dev_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for tests and the one- and four-chip store paths (more
    than one CPU device needs the XLA host-device override in a
    subprocess; see tests/test_distributed.py). Axes are Auto, as
    with_sharding_constraint hints in the model steps require."""
    return jax.make_mesh(
        (n_data, n_model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
