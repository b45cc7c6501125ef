"""Bytes a kernel must move, from the shapes of its call.

These belong to the benchmark, not the program, so that a later change
to a kernel cannot change how its roofline share is counted.
"""
from __future__ import annotations

import math

STRIDE = 16            # anchor stride of cuSZ-Hi's 3-D hierarchy
BLOCK = STRIDE + 1     # closed 17^3 block
LANES = 128            # blocks per grid step of the Pallas kernel


def blocks_of(shape, stride: int = STRIDE) -> int:
    """Blocks of a field: each dim padded up to ``stride * k + 1``, the
    last ``min(ndim, 3)`` dims spatial, the rest folded into a batch."""
    nd = min(len(shape), 3)
    batch = math.prod(shape[: len(shape) - nd])
    return batch * math.prod(max(1, -(-(s - 1) // stride)) for s in shape[len(shape) - nd:])


def interp3d_bytes(n_blocks: int) -> int:
    """HBM bytes of one ``interp3d_compress`` call over ``n_blocks``
    blocks: the f32 blocks read, the int32 codes and the f32
    reconstruction written, each a (17, 17, 17, nb) array with nb padded
    to a multiple of 128 lanes. The step tables (under 1% at 512^3) are
    left out, so the share is never counted high."""
    nb = -(-n_blocks // LANES) * LANES
    return nb * BLOCK ** 3 * (4 + 4 + 4)
