"""Block partitioning for the cuSZ-Hi predictor.

The paper (§5.1.1) partitions the field into isotropic 17^ndim blocks whose
corners are the losslessly-stored anchor points (anchor stride 16 per dim).
Adjacent blocks share their boundary faces; face points are predicted
identically by both owners (a face point's stencil never leaves the face),
so overlapping scatter writes are value-identical and ownership is exact.
"""
from __future__ import annotations

import functools
import itertools

import numpy as np

ANCHOR_STRIDE = 16
BLOCK = ANCHOR_STRIDE + 1  # 17: closed block [0, 16]^ndim


def padded_shape(shape: tuple[int, ...], stride: int = ANCHOR_STRIDE) -> tuple[int, ...]:
    """Each dim padded up to k*stride + 1 so every block is complete."""
    out = []
    for d in shape:
        k = max(1, -(-max(d - 1, 1) // stride))  # ceil((d-1)/stride), >= 1
        out.append(k * stride + 1)
    return tuple(out)


def pad_field(x: np.ndarray, stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Edge-replicate pad to the block grid shape."""
    tgt = padded_shape(x.shape, stride)
    pads = [(0, t - s) for s, t in zip(x.shape, tgt)]
    if all(p == (0, 0) for p in pads):
        return x
    return np.pad(x, pads, mode="edge")


def pad_field_batch(xb: np.ndarray, stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Batched pad_field: (batch, *spatial) -> (batch, *padded)."""
    tgt = padded_shape(xb.shape[1:], stride)
    pads = [(0, 0)] + [(0, t - s) for s, t in zip(xb.shape[1:], tgt)]
    if all(p == (0, 0) for p in pads[1:]):
        return xb
    return np.pad(xb, pads, mode="edge")


def gather_blocks(xp: np.ndarray, stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """(padded field) -> (nb, B, B, ...) overlapping closed blocks.

    nb = prod((dim-1)/stride); block [i] = xp[stride*i : stride*i + B].
    """
    return gather_blocks_batch(xp[None], stride)


def gather_blocks_batch(xpb: np.ndarray, stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Batched gather: (batch, *padded) -> (batch*nb, B, B, ...).

    Block order matches per-item gather_blocks concatenated along axis 0.
    """
    B = stride + 1
    ndim = xpb.ndim - 1
    win = np.lib.stride_tricks.sliding_window_view(xpb, (B,) * ndim, axis=tuple(range(1, ndim + 1)))
    sl = (slice(None),) + tuple(slice(None, None, stride) for _ in range(ndim))
    blocks = win[sl]  # (batch, nb0, nb1, ..., B, B, ...)
    nb = int(np.prod(blocks.shape[1 : 1 + ndim]))
    return np.ascontiguousarray(blocks.reshape((xpb.shape[0] * nb,) + (B,) * ndim))


def block_grid(shape_padded: tuple[int, ...], stride: int = ANCHOR_STRIDE) -> tuple[int, ...]:
    return tuple((d - 1) // stride for d in shape_padded)


def scatter_blocks(blocks: np.ndarray, shape_padded: tuple[int, ...], stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Inverse of gather_blocks. Overlapping faces are value-identical, so each
    block owns its half-open [0, stride)^ndim cells plus the global far faces."""
    return scatter_blocks_batch(blocks, 1, shape_padded, stride)[0]


def scatter_blocks_batch(blocks: np.ndarray, batch: int, shape_padded: tuple[int, ...], stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Batched inverse of gather_blocks_batch: (batch*nb, B..) -> (batch, *padded)."""
    ndim = len(shape_padded)
    nbs = block_grid(shape_padded, stride)
    out = np.empty((batch,) + shape_padded, dtype=blocks.dtype)
    bl = blocks.reshape((batch,) + nbs + (stride + 1,) * ndim)
    nil = (slice(None),)
    for far in itertools.product((False, True), repeat=ndim):
        # destination region: interior cells on non-far dims, last plane on far dims
        dst = tuple(slice(0, shape_padded[d] - 1) if not far[d] else slice(shape_padded[d] - 1, shape_padded[d]) for d in range(ndim))
        # source: all blocks/cells 0..stride-1 on non-far dims; last block, cell=stride on far dims
        src_blk = tuple(slice(None) if not far[d] else slice(nbs[d] - 1, nbs[d]) for d in range(ndim))
        src_cell = tuple(slice(0, stride) if not far[d] else slice(stride, stride + 1) for d in range(ndim))
        sub = bl[nil + src_blk + src_cell]  # (batch, nb0',.., c0',..)
        # interleave block/cell axes -> spatial
        perm = [0]
        for d in range(ndim):
            perm += [1 + d, 1 + ndim + d]
        sub = np.transpose(sub, perm)
        new_shape = (batch,) + tuple(sub.shape[1 + 2 * d] * sub.shape[2 + 2 * d] for d in range(ndim))
        out[nil + dst] = sub.reshape(new_shape)
    return out


@functools.lru_cache(maxsize=16)
def _scatter_index(shape_padded: tuple[int, ...], stride: int = ANCHOR_STRIDE):
    """Flat gather map realizing scatter_blocks as a single take.

    ``idx[p]`` = index into the flattened (nb, B..) block array of the
    value scatter_blocks writes at padded position ``p`` — produced by
    running the numpy scatter over an arange, so the owner choice (and
    therefore the output bytes) is identical to the reference scatter.
    Cached as an int32 *device* array (block volumes are < 2^31): repeat
    callers — one per frame on the sharded path — pay no host->device
    re-upload, and the cache holds 4 bytes/cell for a handful of shapes
    rather than unbounded int64 host copies.
    """
    from .spans import to_device

    nbs = block_grid(shape_padded, stride)
    nb = int(np.prod(nbs))
    B = stride + 1
    src = np.arange(nb * B ** len(shape_padded), dtype=np.int32)
    idx = scatter_blocks(src.reshape((nb,) + (B,) * len(shape_padded)), shape_padded, stride)
    return to_device(idx.reshape(-1))  # uncommitted: follows the operand's device


def scatter_blocks_batch_jnp(blocks, idx, batch: int, shape_padded: tuple[int, ...]):
    """Device twin of scatter_blocks_batch: one gather by ``idx``, the
    :func:`_scatter_index` of ``shape_padded`` (an argument, so a jitted
    caller does not bake it in as a constant).

    ``blocks`` is a jax array shaped (batch*nb, B..); returns the (batch,
    *padded) grid as a device array, bit-identical to the numpy scatter.
    """
    import jax.numpy as jnp

    flat = blocks.reshape(batch, -1)
    return jnp.take(flat, idx, axis=1).reshape((batch,) + tuple(shape_padded))


def gather_blocks_batch_jnp(xpb, stride: int = ANCHOR_STRIDE):
    """Device twin of gather_blocks_batch: (batch, *padded) -> (batch*nb, B..).

    Pure data movement with static indices — bit-identical to the numpy
    sliding-window gather, traceable inside shard_map.
    """
    import jax.numpy as jnp

    B = stride + 1
    ndim = xpb.ndim - 1
    out = xpb
    nbs = []
    for d in range(ndim):
        ax = 1 + d
        nbd = (out.shape[ax] - 1) // stride
        nbs.append(nbd)
        idx = (np.arange(nbd)[:, None] * stride + np.arange(B)[None, :]).reshape(-1)
        out = jnp.take(out, jnp.asarray(idx), axis=ax)
    shp = [out.shape[0]]
    for nbd in nbs:
        shp += [nbd, B]
    out = out.reshape(shp)
    perm = [0] + [1 + 2 * d for d in range(ndim)] + [2 + 2 * d for d in range(ndim)]
    out = jnp.transpose(out, perm)
    return out.reshape((xpb.shape[0] * int(np.prod(nbs)),) + (B,) * ndim)


@functools.lru_cache(maxsize=16)
def _anchor_index(shape_padded: tuple[int, ...], stride: int = ANCHOR_STRIDE):
    """Cached device (idx, mask) realizing place_anchors as a gather.

    ``mask[p]`` marks padded positions whose every coordinate is divisible
    by the stride; ``idx[p]`` is the flat anchor-grid index feeding it
    (0 where masked off). Gather+where instead of a strided scatter — the
    fast direction on XLA:CPU (same trade as _scatter_index).
    """
    from .spans import to_device

    coords = np.meshgrid(*(np.arange(d) for d in shape_padded), indexing="ij")
    mask = np.ones(shape_padded, bool)
    for c in coords:
        mask &= c % stride == 0
    ashape = tuple((d - 1) // stride + 1 for d in shape_padded)
    idx = np.ravel_multi_index(tuple(c // stride for c in coords), ashape).astype(np.int32)
    idx[~mask] = 0
    return to_device(idx.reshape(-1)), to_device(mask.reshape(-1))


def place_anchors_batch_jnp(shape_padded: tuple[int, ...], anchors, ix):
    """Device twin of place_anchors_batch; ``anchors`` is a jax array
    (batch, *anchor_shape), ``ix`` the :func:`_anchor_index` pair;
    returns (batch, *padded) f32, bit-identical."""
    import jax.numpy as jnp

    idx, mask = ix
    flat = anchors.astype(jnp.float32).reshape(anchors.shape[0], -1)
    rows = jnp.take(flat, idx, axis=1)
    out = jnp.where(mask[None, :], rows, jnp.float32(0.0))
    return out.reshape((anchors.shape[0],) + tuple(shape_padded))


def anchor_grid(xp: np.ndarray, stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Losslessly stored anchors: every coordinate divisible by the stride."""
    sl = tuple(slice(None, None, stride) for _ in range(xp.ndim))
    return np.ascontiguousarray(xp[sl])


def anchor_grid_batch(xpb: np.ndarray, stride: int = ANCHOR_STRIDE) -> np.ndarray:
    """Batched anchor_grid: (batch, *padded) -> (batch, *anchor_shape)."""
    sl = (slice(None),) + tuple(slice(None, None, stride) for _ in range(xpb.ndim - 1))
    return np.ascontiguousarray(xpb[sl])


def place_anchors(shape_padded: tuple[int, ...], anchors: np.ndarray, stride: int = ANCHOR_STRIDE, dtype=np.float32) -> np.ndarray:
    out = np.zeros(shape_padded, dtype=dtype)
    sl = tuple(slice(None, None, stride) for _ in range(len(shape_padded)))
    out[sl] = anchors
    return out


def place_anchors_batch(shape_padded: tuple[int, ...], anchors: np.ndarray, stride: int = ANCHOR_STRIDE, dtype=np.float32) -> np.ndarray:
    """Batched place_anchors; `anchors` is (batch, *anchor_shape)."""
    out = np.zeros((anchors.shape[0],) + shape_padded, dtype=dtype)
    sl = (slice(None),) + tuple(slice(None, None, stride) for _ in range(len(shape_padded)))
    out[sl] = anchors
    return out
