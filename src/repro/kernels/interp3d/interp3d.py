"""Pallas TPU kernel: fused interpolation-predict + quantize (paper §5.1).

TPU adaptation of cuSZ-Hi's thread-block-per-17^3-chunk CUDA kernel
(DESIGN.md §3): the data-block axis becomes the vector *lane* axis. Each
grid step stages a (17,17,17,LANES) VMEM tile — LANES independent blocks —
and sweeps the 4-level hierarchy with the operation sequence of
:func:`repro.core.predictor.predict`, so its codes and reconstruction
equal the jax predictor's bit for bit. Each step loops over the leading
spatial dim, one (17,17,LANES) slab at a time (a whole-tile body unrolls
to ~25 MB of code and compiles for minutes): neighbours along the loop
dim are other slabs of the reconstruction, along the middle dim rows of
the slab with the stencil coefficients as Python scalars, along the
sublane dim zero-filled shifts times a (17,1) coefficient column. Blend
weights and level masks are f32 (17,17,17) tables broadcast over the
lanes (Pallas forbids captured array constants, so they ride in as
inputs; u8 tables cannot be lane-broadcast by Mosaic).

VMEM per grid step (LANES=128): a (17,17,17,128) 32-bit block is
3.5 MiB once its 17 sublanes pad to 24. The pipelined I/O (blocks in,
codes and recon out) is 2 x 3 x 3.5 = 21 MiB; the tables are 0.2 MiB
each, 2 x (n_ops + n_steps + 1) of them (2 x 9.8 MiB for the default 3-D
hierarchy); slab-sized live values are 0.2 MiB each. About 41 MiB in
all: past v5e's 16 MiB scoped default, well inside its 128 MiB, hence
``vmem_limit_bytes``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.predictor import (
    CENTER,
    _anchor_mask,
    _shift,
    fence,
    fence_zero,
    quantize_pred,
    step_taps,
)
from repro.core.stencils import Step

LANES = 128
VMEM_LIMIT = 64 * 2**20


@functools.lru_cache(maxsize=None)
def pack_steps(steps: tuple[Step, ...], anchor_every: int):
    """Stack step tables into dense arrays + static dispatch metadata.

    Returns (wts (n_ops,B..) f32, masks (n_steps+1,B..) f32, rows
    (n_rows,B) f32, cols (n_cols,B,1) f32, meta) where meta[k] =
    ((dim, op_idx, taps), ...) for step k, taps = ((offset, coef), ...)
    and masks[0] = anchors. ``coef`` is an index into ``rows`` for dim 0
    (read per grid row), a tuple of Python floats for dim 1, and an index
    into ``cols`` for dim 2 (the sublane dim).
    """
    B = steps[0].mask.shape[0]
    ndim = steps[0].mask.ndim
    assert ndim == 3, "interp3d runs 17^3 blocks"
    wts, rows, cols, meta = [], [], [], []
    masks = [_anchor_mask((B,) * ndim, anchor_every).astype(np.float32)]
    for st in steps:
        ops = []
        for d, M, w in zip(st.dims, st.matrices, st.weights):
            taps = []
            for k, c in step_taps(M):
                if d == 0:
                    taps.append((k, len(rows)))
                    rows.append(c)
                elif d == 1:
                    taps.append((k, tuple(float(v) for v in c)))
                else:
                    taps.append((k, len(cols)))
                    cols.append(c.reshape(B, 1))
            ops.append((d, len(wts), tuple(taps)))
            wts.append(w.astype(np.float32))
        masks.append(st.mask.astype(np.float32))
        meta.append(tuple(ops))
    pad = lambda xs, shape: np.stack(xs) if xs else np.zeros(shape, np.float32)
    return (np.stack(wts), np.stack(masks), pad(rows, (1, B)), pad(cols, (1, B, 1)),
            tuple(meta))


def _kernel(blocks_ref, twoeb_ref, inv2eb_ref, z_ref, rows_ref, wts_ref, masks_ref, cols_ref,
            codes_ref, recon_ref, *, meta):
    """One grid step: LANES blocks. ``recon_ref`` doubles as the working
    reconstruction; each step sweeps the leading spatial dim with a
    fori_loop so every vector op touches one (17,17,LANES) slab."""
    B = blocks_ref.shape[0]
    twoeb = twoeb_ref[0]
    inv2eb = inv2eb_ref[0]
    z = z_ref[0]

    def table(ref, t, i):  # (B,B) slice of a (n,B,B,B) table, broadcast over lanes
        return jnp.broadcast_to(ref[t, i][..., None], (B, B, LANES))

    def init(i, carry):
        recon_ref[i] = jnp.where(table(masks_ref, 0, i) != 0.0, blocks_ref[i], 0.0)
        codes_ref[i] = jnp.full((B, B, LANES), CENTER, jnp.int32)
        return carry

    jax.lax.fori_loop(0, B, init, 0)
    for k, ops in enumerate(meta):
        def body(i, carry, ops=ops, k=k):
            r = recon_ref[i]  # (B,B,L) slab of the reconstruction
            pred = None
            for d, oi, taps in ops:
                pd = None
                if d == 0:  # neighbour slabs along the loop dim
                    for off, ri in taps:
                        j = i + off
                        nb = recon_ref[jnp.clip(j, 0, B - 1)]
                        nb = jnp.where((j >= 0) & (j < B), nb, 0.0)
                        t = fence(rows_ref[ri, i] * nb, z)
                        pd = t if pd is None else pd + t
                elif d == 1:  # rows of the slab, Python-scalar coefficients
                    parts = []
                    for jr in range(B):
                        if not any(coef[jr] for _, coef in taps):  # not a target row
                            parts.append(jnp.zeros_like(r[:1]))
                            continue
                        acc = None
                        for off, coef in taps:
                            src = jr + off
                            nb = r[src] if 0 <= src < B else jnp.zeros_like(r[0])
                            t = fence(jnp.float32(coef[jr]) * nb, z)
                            acc = t if acc is None else acc + t
                        parts.append(acc[None])
                    pd = jnp.concatenate(parts, axis=0)
                else:  # sublane dim: zero-filled shifts x coefficient columns
                    for off, ci in taps:
                        t = fence(cols_ref[ci] * _shift(r, off, 1), z)
                        pd = t if pd is None else pd + t
                t = fence(table(wts_ref, oi, i) * pd, z)
                pred = t if pred is None else pred + t
            code, _, rec = quantize_pred(blocks_ref[i], pred, twoeb, inv2eb, z)  # shared quantizer
            m = table(masks_ref, k + 1, i) != 0.0
            recon_ref[i] = jnp.where(m, rec, r)
            codes_ref[i] = jnp.where(m, code, codes_ref[i])
            return carry

        jax.lax.fori_loop(0, B, body, 0)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def interp3d_compress(blocks_t: jnp.ndarray, twoeb: jnp.ndarray, inv2eb: jnp.ndarray, steps: tuple[Step, ...],
                      anchor_every: int = 16, interpret: bool = True):
    """blocks_t: (B,B,B, nb_padded) with nb_padded % LANES == 0;
    ``(twoeb, inv2eb)`` from :func:`repro.core.predictor.quant_steps`.

    Returns (codes u8, recon f32), same layout; code 0 marks an outlier.
    """
    B = blocks_t.shape[0]
    nb = blocks_t.shape[-1]
    assert nb % LANES == 0, "pad the block axis to a LANES multiple"
    wts, masks, rows, cols, meta = pack_steps(steps, anchor_every)
    twoeb = jnp.asarray(twoeb, jnp.float32).reshape(1)
    inv2eb = jnp.asarray(inv2eb, jnp.float32).reshape(1)
    spec = pl.BlockSpec((B, B, B, LANES), lambda i: (0, 0, 0, i))
    fixed = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shapes = (
        jax.ShapeDtypeStruct(blocks_t.shape, jnp.int32),
        jax.ShapeDtypeStruct(blocks_t.shape, jnp.float32),
    )
    codes, recon = pl.pallas_call(
        functools.partial(_kernel, meta=meta),
        grid=(nb // LANES,),
        in_specs=[spec, smem, smem, smem, smem, fixed(wts.shape), fixed(masks.shape), fixed(cols.shape)],
        out_specs=(spec, spec),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(blocks_t, twoeb, inv2eb, fence_zero(twoeb).reshape(1), jnp.asarray(rows),
      jnp.asarray(wts), jnp.asarray(masks), jnp.asarray(cols))
    return codes.astype(jnp.uint8), recon
