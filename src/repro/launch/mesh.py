"""Production meshes (functions, never module-level constants: importing this
module must not touch jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests / elastic rescale). Axes are Auto: the model
    code places activations with sharding constraints, which Explicit
    axes (jax.make_mesh's default since 0.7) reject."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
