"""What the field generators share: a PRNG key that keeps every bit of a
seed, a seeded spectral field made on the device, and the roll offset of
a run's k-th snapshot.

The spectral field is a copy of the one in ``repro.data.fields`` /
``chip_smoke.py`` (white noise filtered by ``|k|^-alpha``, scaled to
``max|u| = 1``), computed in one jitted call in f32 on the device instead
of a float64 host FFT. The benchmark keeps its own copy so that no later
change to the program can change its inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int):
    """A PRNG key that keeps every bit of a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("shape", "alpha"))
def spectral(k, shape: tuple, alpha: float):
    white = jax.random.normal(k, shape, jnp.float32)
    ks = [jnp.fft.fftfreq(n).astype(jnp.float32) for n in shape[:-1]] + [jnp.fft.rfftfreq(shape[-1]).astype(jnp.float32)]
    k2 = sum(kk.reshape((-1,) + (1,) * (len(shape) - 1 - i)) ** 2 for i, kk in enumerate(ks))
    filt = (k2 + 1e-6) ** (-alpha / 2.0)
    filt = filt.at[(0,) * len(shape)].set(0.0)
    u = jnp.fft.irfftn(jnp.fft.rfftn(white) * filt, s=shape)
    return u / jnp.maximum(jnp.max(jnp.abs(u)), 1e-12)


def shift(seed: int, k: int, shape: tuple) -> np.ndarray:
    """The roll offset of snapshot ``k`` of a run's seed (int32, one per
    axis): any seed, negative or past 64 bits, draws a fixed offset."""
    return np.random.default_rng([seed % 2**64, k]).integers(0, shape).astype(np.int32)
