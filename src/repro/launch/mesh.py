"""Production mesh construction.

A function, not a module-level constant: importing this module never
touches jax device state.  The production target is TPU v5e-style pods:
16x16 = 256 chips per pod, 2 pods = 512 chips for the multi-pod dry run.
"""
from __future__ import annotations

from typing import Optional

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests, elastic scaling), every axis ``Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def host_device_mesh(n: Optional[int] = None, axis: str = "w"):
    """1-D mesh over the live devices — the sharded engine's default shape
    (``QuegelEngine(mesh=host_device_mesh())``).  On CPU, force multiple
    host devices with XLA_FLAGS=--xla_force_host_platform_device_count=N
    *before* importing jax."""
    n = n or len(jax.devices())
    return make_mesh((n,), (axis,))


def elastic_mesh(min_model: int = 4):
    """Build the largest (data, model) mesh from the *live* device list —
    jobs resume after losing hosts by rebuilding the mesh and resharding
    the (logical) checkpoint."""
    n = len(jax.devices())
    model = min(min_model, n)
    while n % model and model > 1:
        model -= 1
    return make_mesh((n // model, model), ("data", "model"))
