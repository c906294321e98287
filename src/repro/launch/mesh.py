"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax use).

Production target: TPU v5e, 256 chips per pod in a (16, 16) (data, model)
mesh; the multi-pod variant adds a leading "pod" axis over 2 pods = 512
chips.  Batch is sharded over ("pod", "data"); weights/experts/heads over
"model" (see launch/shardings.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (sharding left to the compiler)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Small mesh for CPU tests (model*data must be <= available devices)."""
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
