"""Jitted host-facing wrapper for the interp3d Pallas kernel.

This is the ``backend="pallas"`` entry point used by
``repro.core.compressor.Compressor``: interpret mode is auto-selected (the
kernel interprets on CPU/GPU hosts and compiles on TPU). The compiled
kernel runs at 512^3 on a TPU v5e in ``chip_smoke.py`` phase c, where its
container equals the jax predictor's byte for byte.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.predictor import quant_steps
from repro.core.spans import to_device, to_host

from .interp3d import LANES, interp3d_compress


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def compress_blocks_pallas(blocks: np.ndarray, twoeb: float, steps, anchor_every: int = 16, interpret: bool | None = None):
    """Drop-in for repro.core.predictor.compress_blocks, routed through Pallas.

    blocks: (nb, B, B, B) f32 -> (codes u8, outlier bool, recon f32), (nb, B, B, B).
    interpret=None auto-selects: compiled on TPU, interpreter elsewhere.
    """
    if interpret is None:
        interpret = _default_interpret()
    nb = blocks.shape[0]
    pad = (-nb) % LANES
    if pad:
        blocks = np.concatenate([blocks, np.zeros((pad,) + blocks.shape[1:], blocks.dtype)], 0)
    bt = to_device(np.moveaxis(blocks, 0, -1))  # (B,B,B,nb')
    codes, recon = interp3d_compress(bt, *quant_steps(0.5 * twoeb), steps, anchor_every, interpret)
    mv = lambda a: np.moveaxis(to_host(a), -1, 0)[:nb]
    codes = mv(codes)
    return codes, codes == 0, mv(recon)


def compress_blocks_pallas_plan(blocks: np.ndarray, twoeb: float, plan, interpret: bool | None = None):
    """Plan-driven kernel entry: step tables and anchor stride come from a
    ``repro.core.autotune.PredictorPlan`` (interpret and compiled modes both
    honour the plan — pack_steps stacks whatever hierarchy it describes)."""
    return compress_blocks_pallas(blocks, twoeb, plan.steps(blocks.shape[1]), plan.anchor_stride, interpret)
