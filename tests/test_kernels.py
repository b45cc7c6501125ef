"""Per-kernel interpret-mode validation against the pure-jnp/numpy oracles,
sweeping shapes and configurations."""
import numpy as np
import pytest

from repro.core.stencils import build_steps
from repro.kernels.bitshuffle import bitshuffle_pallas, bitshuffle_ref
from repro.kernels.histogram import histogram256_pallas, histogram256_ref
from repro.kernels.interp3d import compress_blocks_pallas, compress_blocks_ref
from repro.kernels.lorenzo3d import lorenzo_encode_pallas, lorenzo_encode_ref


@pytest.mark.parametrize("spline", ["linear", "cubic"])
@pytest.mark.parametrize("scheme", ["md", "1d"])
@pytest.mark.parametrize("nb", [1, 5])
def test_interp3d_matches_ref(spline, scheme, nb):
    rng = np.random.default_rng(nb)
    blocks = rng.standard_normal((nb, 17, 17, 17)).astype(np.float32)
    steps = build_steps(3, 17, (8, 4, 2, 1), (spline,) * 4, (scheme,) * 4)
    ck, ok, rk = compress_blocks_pallas(blocks, 0.01, steps)
    cr, orf, rr = compress_blocks_ref(blocks, 0.01, steps)
    # one operation sequence: codes and reconstruction equal bit for bit
    assert np.array_equal(ck, cr) and np.array_equal(ok, orf)
    assert np.array_equal(rk.view(np.uint32), rr.view(np.uint32))
    assert np.abs(rk - blocks)[~ok].max() <= 0.005  # error bound (non-outlier), exact


@pytest.mark.parametrize("eb", [1e-1, 1e-3])
def test_interp3d_anchor8(eb):
    rng = np.random.default_rng(7)
    blocks = rng.standard_normal((3, 17, 17, 17)).astype(np.float32)
    steps = build_steps(3, 17, (4, 2, 1), ("cubic",) * 3, ("1d",) * 3)
    ck, _, rk = compress_blocks_pallas(blocks, eb, steps, anchor_every=8)
    cr, _, rr = compress_blocks_ref(blocks, eb, steps, anchor_every=8)
    assert np.array_equal(ck, cr)
    assert np.array_equal(rk.view(np.uint32), rr.view(np.uint32))


@pytest.mark.parametrize("shape", [(8, 8, 128), (20, 24, 130), (33, 7, 250)])
@pytest.mark.parametrize("eb", [0.5, 0.01])
def test_lorenzo3d_matches_ref(shape, eb):
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = rng.standard_normal(shape).astype(np.float32)
    ck, ok, cfk = lorenzo_encode_pallas(x, eb)
    cr, orf, cfr = lorenzo_encode_ref(x, eb)
    assert (ck == cr).all() and (ok == orf).all() and (cfk == cfr).all()


@pytest.mark.parametrize("n", [1, 1000, 8192, 100000])
def test_bitshuffle_matches_ref(n):
    d = np.random.default_rng(n).integers(0, 256, n, dtype=np.uint8)
    assert (bitshuffle_pallas(d) == bitshuffle_ref(d)).all()


@pytest.mark.parametrize("n", [1, 8192, 100001])
def test_histogram_matches_ref(n):
    d = np.random.default_rng(n).integers(0, 256, n, dtype=np.uint8)
    assert (histogram256_pallas(d) == histogram256_ref(d)).all()
