"""Pallas TPU kernel: BIT1 bit-plane shuffle (paper §5.2.3).

Per block of ``block`` bytes, output plane p holds bit p (MSB first) of
every byte: payload byte ``(p, q)`` packs bit ``7-p`` of bytes
``8q..8q+7``, MSB first. Viewing the block as (8, block/8) — row ``s``
holding byte ``8q+s`` of every group ``q`` — both directions are the same
8x8 bit-matrix transpose per group, ``y[r, q] = sum_s bit_{7-r}(x[s, q])
<< (7-s)``: shifts and masks on int32 lanes, one group per lane. The
(block/8, 8) -> (8, block/8) byte transpose around the kernel is a plain
XLA transpose, so the kernel's tiles are (8, block/8) with block/8 a
multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024     # bytes per shuffle block
TILE_BLOCKS = 8  # blocks per grid step


def _kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.int32)  # (T, 8, G): row s = byte s of each group
    rows = [x[:, s, :] for s in range(8)]
    out = []
    for r in range(8):
        acc = None
        for s in range(8):
            t = ((rows[s] >> (7 - r)) & 1) << (7 - s)
            acc = t if acc is None else acc | t
        out.append(acc)
    o_ref[...] = jnp.stack(out, axis=1).astype(jnp.uint8)


def _transpose8(x, interpret: bool):
    """(n, 8, G) u8 -> per-group 8x8 bit-matrix transpose, same shape."""
    n = x.shape[0]
    pad = (-n) % TILE_BLOCKS
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    spec = pl.BlockSpec((TILE_BLOCKS,) + x.shape[1:], lambda i: (i, 0, 0))
    y = pl.pallas_call(
        _kernel,
        grid=(x.shape[0] // TILE_BLOCKS,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint8),
        interpret=interpret,
    )(x)
    return y[:n]


@functools.partial(jax.jit, static_argnums=(1,))
def bitshuffle_pallas_raw(x: jnp.ndarray, interpret: bool = True):
    """x: (nblocks, block) u8, block % 1024 == 0 -> plane-major payload.

    The block size is taken from ``x.shape[1]``, so the device encoding
    engine runs the host encoder's 8192-byte-block layout through the same
    kernel as the default 1024-byte call sites.
    """
    n, block = x.shape
    xt = jnp.swapaxes(x.reshape(n, block // 8, 8), 1, 2)  # (n, 8, G)
    return _transpose8(xt, interpret).reshape(n, block)


@functools.partial(jax.jit, static_argnums=(1,))
def bitunshuffle_pallas_raw(x: jnp.ndarray, interpret: bool = True):
    """Inverse of :func:`bitshuffle_pallas_raw` (same layout contract)."""
    n, block = x.shape
    y = _transpose8(x.reshape(n, 8, block // 8), interpret)
    return jnp.swapaxes(y, 1, 2).reshape(n, block)
