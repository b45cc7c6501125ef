"""Interpolation-based lossy decomposition (paper §5.1) — pure-JAX engine.

Runs the 4-level hierarchical spline prediction over a batch of closed
17^ndim blocks (block axis vectorized), quantizes prediction errors to
uint8 codes (radius 127, code 0 reserved for outliers, paper §5.2.1) and
maintains the reconstruction in lock-step so compression and decompression
replay bit-identical arithmetic.

Platform-exact arithmetic: an archive written on one backend must decode
to the same floats on another, so the reconstruction is a fixed sequence
of single IEEE-754 f32 operations. Each 1-D interpolation is a sum of
shifted neighbours times per-row stencil coefficients (the rows of the
stencils.py step matrices), and each product passes through
:func:`fence` before it is added. The fence is an identity that no
compiler can see through, so XLA:CPU cannot contract ``a*b + c`` into an
FMA (which TPUs never do) and every value is rounded exactly as IEEE
prescribes on CPU and TPU alike. No matmul is involved: an MXU pass and
XLA:CPU's dot round differently even at ``Precision.HIGHEST``, and no
division either, since TPU division is not correctly rounded.

The quantizer's reciprocal step ``inv2eb`` comes from the host
(:func:`quant_steps`), for the same reason.

Containers written before this arithmetic (no ``arith`` header field)
were encoded with the matmul form of the steps on XLA:CPU; they replay
through :func:`decompress_blocks_matmul`, which the compressor runs on the
CPU device so those archives keep decoding to the same floats.

The Pallas kernel in repro.kernels.interp3d runs the same operation
sequence with the block axis on the TPU lanes. This module is the
reference/runtime engine used by the host compressor (and the oracle the
kernel is tested against).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .stencils import Step, build_steps

RADIUS = 127
CENTER = 128  # uint8 code = q + 128; 0 marks an outlier
ARITH = 1  # container "arith" field: the fenced, matmul-free arithmetic


def quant_steps(eb_abs):
    """The quantizer's step ``twoeb`` and its reciprocal ``inv2eb``, both
    f32, the reciprocal by an IEEE f32 division on the host. Every encoder
    takes both, so a CPU and a TPU encode quantize with the same
    ``inv2eb`` and write the same codes.

    The step is ``2 * eb_abs`` shortened by 2^-13: a point exactly between
    two codes (common where neighbours were quantized to multiples of the
    step, as in sparse fields) then reconstructs 2^-14 * eb inside the
    bound rather than on it, where f32 rounding would push half of such
    points past it and :func:`quantize_pred` would store them as outliers.
    """
    twoeb = np.float32(2.0 * (1.0 - 2.0**-13) * np.asarray(eb_abs, np.float64))
    return twoeb, np.float32(1.0) / twoeb


def fence_zero(twoeb: jnp.ndarray) -> jnp.ndarray:
    """A runtime int32 zero derived from the (positive) quantization step:
    its sign bit. Being data, it cannot be constant-folded, which is what
    makes :func:`fence` opaque to the compiler."""
    return lax.shift_right_logical(lax.bitcast_convert_type(jnp.asarray(twoeb, jnp.float32), jnp.int32), 31)


def fence(x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Identity on f32 ``x`` (``z`` is :func:`fence_zero`) that stops the
    compiler from fusing the product ``x`` into a following add."""
    return lax.bitcast_convert_type(lax.bitcast_convert_type(x, jnp.int32) ^ z, jnp.float32)


def step_taps(M: np.ndarray) -> tuple[tuple[int, np.ndarray], ...]:
    """Row stencils of a (B,B) step matrix as ``(offset, coef[B])`` pairs in
    ascending offset order: ``(M @ x)[i] == sum_k coef_k[i] * x[i + k]``."""
    B = M.shape[0]
    rows, cols = np.nonzero(M)
    taps = []
    for k in sorted(set((cols - rows).tolist())):
        c = np.zeros(B, np.float32)
        i = np.arange(max(0, -k), min(B, B - k))
        c[i] = M[i, i + k]
        taps.append((int(k), c))
    return tuple(taps)


def _shift(x: jnp.ndarray, k: int, axis: int) -> jnp.ndarray:
    """``y[.., i, ..] = x[.., i + k, ..]`` along ``axis``, zero-filled."""
    return _taps_of(x, (k,), axis)[0]


def _taps_of(x: jnp.ndarray, offsets, axis: int) -> list:
    """Zero-filled shifts of ``x`` by each of ``offsets`` along ``axis``:
    one pad, then a static slice per offset (cheap for XLA to compile)."""
    n = x.shape[axis]
    lo, hi = max(0, -min(offsets)), max(0, max(offsets))
    cfg = [(0, 0, 0)] * x.ndim
    cfg[axis] = (lo, hi, 0)
    xp = lax.pad(x, jnp.zeros((), x.dtype), cfg)
    return [lax.slice_in_dim(xp, lo + k, lo + k + n, axis=axis) for k in offsets]


def _along(v: np.ndarray, axis: int, ndim: int) -> jnp.ndarray:
    shape = [1] * ndim
    shape[axis] = v.shape[0]
    return jnp.asarray(v.reshape(shape))


def predict(recon: jnp.ndarray, step: Step, z: jnp.ndarray) -> jnp.ndarray:
    """One step's prediction over (nb, B..) blocks:
    ``sum_d fence(w_d * sum_k fence(coef_k * shift_k(recon)))``, terms
    added in ascending dim and offset order."""
    pred = None
    for d, M, w in zip(step.dims, step.matrices, step.weights):
        taps = step_taps(M)
        pd = None
        for (k, c), xs in zip(taps, _taps_of(recon, [k for k, _ in taps], d + 1)):
            t = fence(_along(c, d + 1, recon.ndim) * xs, z)
            pd = t if pd is None else pd + t
        t = fence(jnp.asarray(w)[None] * pd, z)
        pred = t if pred is None else pred + t
    return pred


def quantize_pred(orig, pred, twoeb, inv2eb, z):
    """The quantizer: (code u8-valued i32 with 0 = outlier, outlier mask,
    feedback reconstruction). Single source of truth for the arithmetic —
    the engine below, the autotuner's trial passes, and the Pallas kernel
    all call this, so their code streams stay bit-identical.

    A point is an outlier unless its code is in range and its rounded
    reconstruction meets the bound, so every non-outlier decodes within
    the bound exactly, not just up to f32 rounding. With ``twoeb`` from
    :func:`quant_steps` the bound ``twoeb * (1 + 2^-14) / 2`` is at most
    ``eb_abs``.
    """
    q = jnp.rint((orig - pred) * inv2eb)
    rec = pred + fence(q * twoeb, z)
    # written as "not within": a NaN prediction (inf - inf near the f32
    # limit) fails both tests and becomes an outlier
    outl = ~((jnp.abs(q) <= RADIUS) & (jnp.abs(orig - rec) <= twoeb * np.float32(0.5 + 2.0**-15)))
    rec = jnp.where(outl, orig, rec)
    qi = jnp.clip(q, -RADIUS - 1, RADIUS + 1).astype(jnp.int32)  # safe cast; outliers coded 0
    code = jnp.where(outl, 0, qi + CENTER)
    return code, outl, rec


def _anchor_mask(spatial: tuple[int, ...], anchor_every: int) -> np.ndarray:
    m = np.zeros(spatial, bool)
    sl = tuple(slice(None, None, anchor_every) for _ in spatial)
    m[sl] = True
    return m


# Blocks per predictor pass. A (nb, 17, 17, 17) pass keeps about 28
# block-sized f32 temporaries live (XLA:TPU memory_analysis of
# compress_blocks: 18.4 GB at nb = 32768, the 512^3 field, against a
# v5e's 16 GB), so larger batches run as a sequential lax.map over
# chunks of this many blocks. The arithmetic is elementwise per block,
# so chunking leaves every value unchanged.
PRED_BATCH = 4096


def _chunked(core, nb: int, *xs):
    if nb <= PRED_BATCH:
        return core(*xs)
    per_block = lambda args: jax.tree.map(lambda a: a[0], core(*(a[None] for a in args)))
    return jax.lax.map(per_block, xs, batch_size=PRED_BATCH)


@functools.partial(jax.jit, static_argnums=(3, 4))
def compress_blocks(blocks: jnp.ndarray, twoeb: jnp.ndarray, inv2eb: jnp.ndarray, steps: tuple[Step, ...],
                    anchor_every: int = 16):
    """blocks: (nb, B..) f32 with anchors in place; ``(twoeb, inv2eb)``
    from :func:`quant_steps`.

    Returns (codes u8 (nb,B..), outlier_mask bool, recon f32).
    recon == what the decompressor reproduces (outliers patched exactly).
    """
    anchor_mask = jnp.asarray(_anchor_mask(blocks.shape[1:], anchor_every))
    z = fence_zero(twoeb)

    def core(orig):
        # start from anchors only; non-anchor entries are dead until predicted
        recon = jnp.where(anchor_mask, orig, 0.0)
        codes = jnp.full(orig.shape, CENTER, jnp.int32)
        outl_all = jnp.zeros(orig.shape, bool)
        for step in steps:
            pred = predict(recon, step, z)
            code, outl, rec = quantize_pred(orig, pred, twoeb, inv2eb, z)
            m = jnp.asarray(step.mask)
            recon = jnp.where(m, rec, recon)
            codes = jnp.where(m, code, codes)
            outl_all = outl_all | (m & outl)
        return codes.astype(jnp.uint8), outl_all, recon

    return _chunked(core, blocks.shape[0], blocks)


@functools.partial(jax.jit, static_argnums=(4, 5))
def decompress_blocks(
    codes: jnp.ndarray,      # (nb, B..) u8, anchors position value irrelevant
    anchors: jnp.ndarray,    # (nb, B..) f32, valid only at anchor positions
    outlier_vals: jnp.ndarray,  # (nb, B..) f32, valid only where code == 0
    twoeb: jnp.ndarray,
    steps: tuple[Step, ...],
    anchor_every: int = 16,
) -> jnp.ndarray:
    anchor_mask = jnp.asarray(_anchor_mask(codes.shape[1:], anchor_every))
    z = fence_zero(twoeb)

    def core(codes, anchors, outlier_vals):
        recon = jnp.where(anchor_mask, anchors, 0.0)
        q = codes.astype(jnp.int32) - CENTER
        is_outl = codes == 0
        for step in steps:
            pred = predict(recon, step, z)
            rec = jnp.where(is_outl, outlier_vals, pred + fence(q.astype(jnp.float32) * twoeb, z))
            recon = jnp.where(jnp.asarray(step.mask), rec, recon)
        return recon

    return _chunked(core, codes.shape[0], codes, anchors, outlier_vals)


def _apply_mat(recon: jnp.ndarray, M: np.ndarray, axis: int) -> jnp.ndarray:
    """Apply (B,B) operator along spatial `axis` of (nb, B, ..., B)."""
    x = jnp.moveaxis(recon, axis + 1, 0)  # (B, nb, ...)
    y = jnp.tensordot(jnp.asarray(M), x, axes=((1,), (0,)), precision=lax.Precision.HIGHEST)
    return jnp.moveaxis(y, 0, axis + 1)


@functools.partial(jax.jit, static_argnums=(4, 5))
def decompress_blocks_matmul(codes, anchors, outlier_vals, twoeb, steps: tuple[Step, ...], anchor_every: int = 16):
    """:func:`decompress_blocks` for containers without an ``arith``
    field: the matmul form their encoder ran. Bit-exact only on XLA:CPU,
    where those archives were written."""
    recon = jnp.where(jnp.asarray(_anchor_mask(codes.shape[1:], anchor_every)), anchors, 0.0)
    q = codes.astype(jnp.int32) - CENTER
    is_outl = codes == 0
    for step in steps:
        pred = jnp.zeros_like(recon)
        for d, M, w in zip(step.dims, step.matrices, step.weights):
            pred = pred + jnp.asarray(w) * _apply_mat(recon, M, d)
        rec = jnp.where(is_outl, outlier_vals, pred + q.astype(jnp.float32) * twoeb)
        recon = jnp.where(jnp.asarray(step.mask), rec, recon)
    return recon


def default_steps(ndim: int, splines=("cubic",) * 4, schemes=("md",) * 4, levels=(8, 4, 2, 1), B: int = 17):
    return build_steps(ndim, B, tuple(levels), tuple(splines), tuple(schemes))
