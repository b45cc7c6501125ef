"""Mapping-based quantization-code reordering (paper §5.1.4, Eq. 3).

Codes are emitted grouped by interpolation level — largest strides first —
row-major within each level. This is the same bijection as the paper's
closed-form index I(x,y,z); we materialize it once per field shape (cached)
and apply it as a gather. Anchor positions (every coord divisible by 16)
carry no quantization code and are excluded (they are stored losslessly).
"""
from __future__ import annotations

import functools

import numpy as np

ANCHOR_STRIDE = 16


@functools.lru_cache(maxsize=64)
def _level_of_shape(shape: tuple[int, ...], stride: int) -> np.ndarray:
    """Per-point hierarchy level: max l<=log2(stride) with 2^l | every coord."""
    lmax = int(np.log2(stride))
    lev = None
    for d in shape:
        c = np.arange(d)
        ld = np.full(d, 0, np.int8)
        for l in range(1, lmax + 1):
            ld[c % (1 << l) == 0] = l
        lev_d = ld
        lev = lev_d if lev is None else np.minimum(lev[..., None], lev_d)
    return lev  # shape `shape`, values 0..lmax


@functools.lru_cache(maxsize=64)
def level_permutation(shape: tuple[int, ...], stride: int = ANCHOR_STRIDE):
    """(perm, inv): perm[j] = flat index (row-major, in `shape`) of the j-th
    code in the reordered sequence; inv undoes it. Anchors excluded."""
    lev = _level_of_shape(shape, stride).reshape(-1)
    lmax = int(np.log2(stride))
    parts = [np.flatnonzero(lev == l) for l in range(lmax - 1, -1, -1)]  # big strides first
    perm = np.concatenate(parts).astype(np.int64)
    # inverse: pos[flat index] = position within the reordered sequence (-1 for anchors)
    pos = np.empty(int(np.prod(shape)), np.int64)
    pos.fill(-1)
    pos[perm] = np.arange(perm.size)
    return perm, pos


@functools.lru_cache(maxsize=64)
def flat_permutation(shape: tuple[int, ...], stride: int = ANCHOR_STRIDE):
    """Non-anchor indices in plain row-major order (the no-reorder ablation)."""
    perm, _ = level_permutation(shape, stride)
    return np.sort(perm)


def reorder_codes(codes_grid: np.ndarray, stride: int = ANCHOR_STRIDE, reorder: bool = True) -> np.ndarray:
    perm = level_permutation(codes_grid.shape, stride)[0] if reorder else flat_permutation(codes_grid.shape, stride)
    return codes_grid.reshape(-1)[perm]


def restore_codes(seq: np.ndarray, shape: tuple[int, ...], fill, dtype, stride: int = ANCHOR_STRIDE, reorder: bool = True) -> np.ndarray:
    perm = level_permutation(shape, stride)[0] if reorder else flat_permutation(shape, stride)
    out = np.full(int(np.prod(shape)), fill, dtype=dtype)
    out[perm] = seq
    return out.reshape(shape)


def reorder_codes_batch(grids: np.ndarray, stride: int = ANCHOR_STRIDE, reorder: bool = True) -> np.ndarray:
    """Batched reorder: (batch, *shape) -> concatenated per-item sequences.

    One cached-permutation gather across the whole batch; identical to
    concatenating per-item reorder_codes results.
    """
    shape = grids.shape[1:]
    perm = level_permutation(shape, stride)[0] if reorder else flat_permutation(shape, stride)
    return grids.reshape(grids.shape[0], -1)[:, perm].reshape(-1)


def reorder_codes_batch_device(grids, stride: int = ANCHOR_STRIDE, reorder: bool = True):
    """Device twin of reorder_codes_batch: the cached host permutation
    applied as one jnp gather; ``grids`` is a jax array (batch, *shape)."""
    import jax.numpy as jnp

    from .spans import to_device

    shape = tuple(int(s) for s in grids.shape[1:])
    perm = level_permutation(shape, stride)[0] if reorder else flat_permutation(shape, stride)
    return jnp.take(grids.reshape(grids.shape[0], -1), to_device(perm), axis=1).reshape(-1)


def restore_codes_batch(seq: np.ndarray, batch: int, shape: tuple[int, ...], fill, dtype, stride: int = ANCHOR_STRIDE, reorder: bool = True) -> np.ndarray:
    """Batched inverse of reorder_codes_batch -> (batch, *shape) grids."""
    perm = level_permutation(shape, stride)[0] if reorder else flat_permutation(shape, stride)
    out = np.full((batch, int(np.prod(shape))), fill, dtype=dtype)
    out[:, perm] = seq.reshape(batch, perm.size)
    return out.reshape((batch,) + shape)


@functools.lru_cache(maxsize=64)
def _restore_gather(shape: tuple[int, ...], stride: int, reorder: bool):
    """Cached device (idx, mask) realizing restore_codes_batch as a gather.

    ``idx[p]`` = sequence position of the code at flat grid index p (0 at
    anchors, masked off); the inverse-scatter becomes take+where, which is
    the fast direction on XLA:CPU (its scatters run ~10x behind gathers).
    """
    from .spans import to_device

    if reorder:
        pos = level_permutation(shape, stride)[1]
    else:
        perm = flat_permutation(shape, stride)
        pos = np.full(int(np.prod(shape)), -1, np.int64)
        pos[perm] = np.arange(perm.size)
    idx = np.where(pos >= 0, pos, 0).astype(np.int32)
    return to_device(idx), to_device(pos >= 0)


def restore_codes_batch_device(seq, ix, batch: int, shape: tuple[int, ...], fill):
    """Device twin of restore_codes_batch over a uint8 device sequence;
    ``ix`` is the :func:`_restore_gather` pair of the container's shape,
    stride and reorder flag.

    Returns the (batch, *shape) uint8 grids as a device array, bit-identical
    to the numpy restore (anchor positions carry ``fill``).
    """
    import jax.numpy as jnp

    idx, mask = ix
    rows = jnp.take(seq.reshape(batch, -1), idx, axis=1)
    out = jnp.where(mask[None, :], rows, jnp.uint8(fill))
    return out.reshape((batch,) + tuple(shape))
