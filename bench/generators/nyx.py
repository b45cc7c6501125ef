"""SDRBench NYX-class density: ``exp(2u)`` of one spectral field ``u``
(slope ``spectral_slope``, ``max|u| = 1``) from the configuration's fixed
``seed``, one dataset as SDRBench ships one NYX field.

Snapshot ``k`` of a run is that field rolled by an offset drawn from the
run's seed and ``k``: it holds the same values in other places, so it is
content no earlier call has seen, with the same statistics and the same
value range, and every seed does the same work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import fields


def base(field: dict):
    shape = tuple(field["shape"])
    return fields.spectral(fields.key(int(field["seed"])), shape, float(field["spectral_slope"]))


@jax.jit
def _density(u, offset):
    return jnp.exp(2.0 * jnp.roll(u, tuple(offset[i] for i in range(u.ndim)), tuple(range(u.ndim))))


def snapshot(u, field: dict, seed: int, k: int):
    return _density(u, jnp.asarray(fields.shift(seed, k, tuple(field["shape"]))))
