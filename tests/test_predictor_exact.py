"""The predictor's arithmetic is a fixed sequence of single IEEE-754 f32
operations, so an archive decodes to the same floats on any backend.

XLA:CPU fuses ``a*b + c`` into an FMA and its dot rounds differently from
a TPU's MXU even at ``Precision.HIGHEST``; the predictor therefore uses
no matmul and fences every product. These tests hold XLA:CPU to a numpy
float32 replay of the same sequence (numpy rounds every operation), which
is what a TPU computes as well.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import predictor as P
from repro.core.stencils import build_steps


def _np_shift(x, k, axis):
    y = np.zeros_like(x)
    n = x.shape[axis]
    src = [slice(None)] * x.ndim
    dst = [slice(None)] * x.ndim
    src[axis] = slice(max(k, 0), n + min(k, 0))
    dst[axis] = slice(max(-k, 0), n - max(k, 0))
    y[tuple(dst)] = x[tuple(src)]
    return y


def _np_predict(recon, step):
    pred = None
    for d, M, w in zip(step.dims, step.matrices, step.weights):
        pd = None
        for k, c in P.step_taps(M):
            shape = [1] * recon.ndim
            shape[d + 1] = c.size
            t = c.reshape(shape) * _np_shift(recon, k, d + 1)
            pd = t if pd is None else pd + t
        t = w[None] * pd
        pred = t if pred is None else pred + t
    return pred


def _np_decode(codes, anchors, outlier_vals, twoeb, steps, anchor_every):
    recon = np.where(P._anchor_mask(codes.shape[1:], anchor_every), anchors, np.float32(0))
    q = (codes.astype(np.int32) - P.CENTER).astype(np.float32)
    for step in steps:
        rec = np.where(codes == 0, outlier_vals, _np_predict(recon, step) + q * twoeb)
        recon = np.where(step.mask, rec, recon).astype(np.float32)
    return recon


def _blocks(nb, seed=0):
    return np.exp(np.random.default_rng(seed).standard_normal((nb, 17, 17, 17))).astype(np.float32)


@pytest.mark.parametrize("spline,scheme", [("cubic", "md"), ("linear", "1d-210")])
def test_xla_cpu_replays_numpy_f32_bit_for_bit(spline, scheme):
    steps = build_steps(3, 17, (8, 4, 2, 1), (spline,) * 4, (scheme,) * 4)
    blocks = _blocks(6)
    twoeb, inv2eb = P.quant_steps(1e-3)
    codes, _, recon = P.compress_blocks(jnp.asarray(blocks), twoeb, inv2eb, steps, 16)
    codes, recon = np.asarray(codes), np.asarray(recon)
    ref = _np_decode(codes, blocks, blocks, twoeb, steps, 16)
    assert np.array_equal(recon.view(np.uint32), ref.view(np.uint32))
    dec = np.asarray(P.decompress_blocks(jnp.asarray(codes), jnp.asarray(blocks), jnp.asarray(blocks),
                                         jnp.float32(twoeb), steps, 16))
    assert np.array_equal(dec.view(np.uint32), ref.view(np.uint32))


def test_every_non_outlier_meets_the_bound_exactly():
    steps = P.default_steps(3)
    blocks = _blocks(6, seed=3) * np.float32(7.0)  # large values: ulp is a large share of eb
    eb = np.float32(1e-4)
    codes, outl, recon = P.compress_blocks(jnp.asarray(blocks), *P.quant_steps(eb), steps, 16)
    err = np.abs(np.asarray(recon, np.float64) - blocks)
    assert err.max() <= eb
    assert np.array_equal(np.asarray(outl), np.asarray(codes) == 0)


def test_half_step_ties_code_within_the_bound():
    """A prediction halfway between two codes (neighbours quantized to
    multiples of the step, as in sparse fields) is coded, not stored as an
    outlier, and still decodes within the bound."""
    eb = np.random.default_rng(4).uniform(1e-3, 10.0, 64).astype(np.float32)
    twoeb, inv2eb = P.quant_steps(eb)
    pred = ((np.arange(-120, 120) + 0.5)[:, None] * twoeb[None, :]).astype(np.float32)
    orig = np.zeros_like(pred)
    code, outl, rec = P.quantize_pred(jnp.asarray(orig), jnp.asarray(pred), twoeb, inv2eb, P.fence_zero(twoeb[0]))
    assert not np.asarray(outl).any()
    assert (np.abs(np.asarray(rec, np.float64)) <= eb[None, :]).all()


def test_predictor_has_no_matmul():
    steps = P.default_steps(3)
    b = jnp.zeros((2, 17, 17, 17), jnp.float32)
    for jaxpr in (jax.make_jaxpr(lambda x: P.compress_blocks(x, jnp.float32(1.0), jnp.float32(1.0), steps, 16))(b),
                  jax.make_jaxpr(lambda x: P.decompress_blocks(x.astype(jnp.uint8), x, x, jnp.float32(1.0),
                                                               steps, 16))(b)):
        assert "dot_general" not in str(jaxpr)


def test_chunked_passes_match_one_pass(monkeypatch):
    steps = P.default_steps(3)
    blocks = jnp.asarray(_blocks(6, seed=5))
    qs = P.quant_steps(1e-3)
    whole = P.compress_blocks(blocks, *qs, steps, 16)
    monkeypatch.setattr(P, "PRED_BATCH", 4)  # 6 blocks: one chunk of 4, one padded chunk
    fresh = jax.jit(lambda b, t, i: P.compress_blocks.__wrapped__(b, t, i, steps, 16))  # retraced
    assert "scan" in str(jax.make_jaxpr(fresh)(blocks, *qs))  # the lax.map path ran
    for a, b in zip(whole, fresh(blocks, *qs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _field():
    return np.exp(np.random.default_rng(9).standard_normal((20, 24, 28))).astype(np.float32)


def test_new_containers_record_their_arithmetic():
    from repro.core.compressor import Compressor, _sections_unpack

    header, _ = _sections_unpack(Compressor(eb=1e-3, autotune=False).compress(_field()))
    assert header["mode"] == "interp" and header["arith"] == P.ARITH


@pytest.mark.parametrize("arith", [None, P.ARITH + 1])
def test_container_arithmetic_selects_the_replay(arith):
    """A container without ``arith`` (written before the fenced predictor)
    replays the matmul form, giving the parent's own decode; one from a
    later arithmetic is refused."""
    import pathlib

    from repro.core.compressor import Compressor, _sections_pack, _sections_unpack

    data = pathlib.Path(__file__).parent / "data"
    comp = Compressor(eb=1e-2, pipeline="cr", autotune=False)
    if arith is None:
        blob = (data / "golden_v2.bin").read_bytes()
        assert "arith" not in _sections_unpack(blob)[0]
        out = comp.decompress(blob)
        assert np.array_equal(out.view(np.uint32), np.load(data / "golden_decoded.npy").view(np.uint32))
    else:
        header, sections = _sections_unpack(comp.compress(_field()))
        with pytest.raises(ValueError, match="arithmetic"):
            comp.decompress(_sections_pack(dict(header, arith=arith), sections))
