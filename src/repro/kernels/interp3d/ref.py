"""Pure-jnp oracle for the interp3d kernel: the core predictor itself."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.predictor import compress_blocks, quant_steps


def compress_blocks_ref(blocks: np.ndarray, twoeb: float, steps, anchor_every: int = 16):
    codes, outl, recon = compress_blocks(jnp.asarray(blocks), *quant_steps(0.5 * twoeb), steps, anchor_every)
    return np.asarray(codes), np.asarray(outl), np.asarray(recon)
