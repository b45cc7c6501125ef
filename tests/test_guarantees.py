"""Guaranteed-bound runtime: the chaos-suite acceptance tests.

Two guarantees, asserted end to end:

* **non-finite-safe ingest** — a field with NaN/Inf round-trips through
  every entry path (``Compressor``, ``shard_compress``, ``repro.io``,
  ``compressd``) with the non-finite points restored bit-exactly and the
  finite points within the declared bound;
* **runtime bound verification** — an injected encoder fault
  (:func:`repro.testing.faults.perturb_quant_codes`) that silently
  violates the bound is caught by ``verify="sample"``, repaired within
  the bounded retry ladder (surfaced in ``last_telemetry``), and raises
  a typed :class:`repro.core.errors.BoundViolationError` when the ladder
  is exhausted.
"""
import numpy as np
import pytest

from repro.core import Compressor, CompressorSpec, shard_compress, shard_decompress
from repro.core.errors import BoundViolationError
from repro.testing import perturb_quant_codes
from repro.testing.faults import fault_rng


def _field(shape=(32, 32, 32), seed=None):
    rng = fault_rng(seed)
    x = rng.standard_normal(shape)
    for ax in range(x.ndim):
        x = np.cumsum(x, axis=ax)
    return (x / max(1.0, float(np.max(np.abs(x))))).astype(np.float32)


def _poison(x):
    x = x.copy()
    x[0, :2] = np.nan
    x[3, 4, 5] = np.inf
    x[-1, -1, -1] = -np.inf
    return x


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def _assert_nfsafe_roundtrip(x, y, eb_rel):
    y = np.asarray(y)
    fin = np.isfinite(x)
    assert np.array_equal(_bits(x[~fin]), _bits(y[~fin]))
    xf, yf = x[fin].astype(np.float64), y[fin].astype(np.float64)
    rng = float(np.max(xf)) - float(np.min(xf))
    assert np.max(np.abs(xf - yf)) <= eb_rel * rng * (1 + 2e-4)


# --------------------------------------------------- entry path 1: Compressor
def test_nfsafe_compressor():
    x = _poison(_field())
    comp = Compressor(CompressorSpec(eb=1e-3))
    buf = comp.compress(x)
    tel = comp.last_telemetry
    assert tel["nonfinite"]["n"] == 66  # 2*32 NaN + 2 Inf
    _assert_nfsafe_roundtrip(x, comp.decompress(buf), 1e-3)


def test_nfsafe_inspect_exposes_inner():
    x = _poison(_field())
    comp = Compressor(CompressorSpec(eb=1e-3))
    info = Compressor.inspect(comp.compress(x))
    assert info["mode"] == "nfsafe"
    assert info["inner"]["mode"] == "interp"


# ----------------------------------------------- entry path 2: shard_compress
def test_nfsafe_shard_compress():
    x = _poison(_field())
    comp = Compressor(CompressorSpec(eb=1e-3))
    buf = shard_compress(x, compressor=comp)
    tel = comp.last_telemetry or {}
    import jax

    if jax.device_count() > 1 and x.shape[0] % jax.device_count() == 0:
        # the device pass has no nfsafe stage: it must detect the poison in
        # its min/max reduction and route to per-chunk host compression
        assert tel["shard"]["path"] == "chunk_compress"
        assert tel["shard"]["reason"] == "non-finite values"
    else:
        assert tel["shard"]["path"] == "chunk_compress"
    _assert_nfsafe_roundtrip(x, shard_decompress(buf), 1e-3)


# ---------------------------------------------------- entry path 3: repro.io
def test_nfsafe_io_write(tmp_path):
    from repro.io import rw
    from repro.io.dataset import Dataset

    x = _poison(_field((24, 30, 16)))
    p = str(tmp_path / "nf.cszh")
    rw.write(Dataset({"t2m": x}), p, compression="lossy,rel,1e-3")
    _assert_nfsafe_roundtrip(x, rw.read_variable(p, "t2m"), 1e-3)


# ------------------------------------------------- entry path 3b: checkpoint
def test_nfsafe_checkpoint_codec():
    from repro.checkpoint.codec import decode_tensor, encode_tensor

    x = _poison(_field((32, 32, 8)))
    payload, meta = encode_tensor(x, eb=1e-3)
    assert meta["mode"] == "cuszhi3"  # took the lossy path, not a silent raw fallback
    _assert_nfsafe_roundtrip(x, decode_tensor(payload, meta), 1e-3)


# --------------------------------------------------- entry path 4: compressd
def test_nfsafe_compressd():
    from repro.launch.compressd import CompressdClient, CompressdServer

    x = _poison(_field((24, 24, 24)))
    with CompressdServer("127.0.0.1:0", workers=2) as srv:
        srv.start()
        with CompressdClient(srv.address) as c:
            buf = c.compress(x, spec="lossy,rel,1e-3,verify=sample")
            _assert_nfsafe_roundtrip(x, c.decompress(buf), 1e-3)


def test_all_nonfinite_field_trivial_container():
    x = np.full((64, 64), np.inf, np.float32)
    x[1::3] = np.nan
    x[2::3] = -np.inf
    comp = Compressor(CompressorSpec(eb=1e-3))
    buf = comp.compress(x)
    assert len(buf) < 512
    assert np.array_equal(_bits(x), _bits(comp.decompress(buf)).reshape(x.shape))


# --------------------------------------------------------- verify and repair
def test_injected_violation_caught_and_repaired():
    # 32^3 < the verify sample size, so sampling covers every point: the
    # injected violation cannot slip through
    x = _field()
    comp = Compressor(CompressorSpec(eb=1e-3, verify="sample"))
    with perturb_quant_codes(n_calls=1, delta=8, frac=0.02) as stats:
        buf = comp.compress(x)
    assert stats["perturbed"] > 0
    tel = comp.last_telemetry
    assert tel["verify"]["mode"] == "sample"
    assert tel["verify"]["repairs"] >= 1  # the fault was seen and repaired
    y = comp.decompress(buf)
    rng = float(np.max(x)) - float(np.min(x))
    assert np.max(np.abs(x.astype(np.float64) - y.astype(np.float64))) <= 1e-3 * rng * (1 + 2e-4)


def test_injected_violation_off_mode_is_silent():
    """Sanity check on the injector itself: with verify=off the perturbed
    container really does violate the bound (i.e. the repair test above is
    exercising a genuine violation, not a benign shuffle)."""
    x = _field()
    comp = Compressor(CompressorSpec(eb=1e-3, verify="off"))
    with perturb_quant_codes(n_calls=1, delta=8, frac=0.02) as stats:
        buf = comp.compress(x)
    assert stats["perturbed"] > 0
    y = comp.decompress(buf)
    rng = float(np.max(x)) - float(np.min(x))
    assert np.max(np.abs(x.astype(np.float64) - y.astype(np.float64))) > 1e-3 * rng


def test_persistent_fault_exhausts_ladder():
    # a fault armed for every call survives each repair re-encode; the
    # ladder must give up with the typed error, never return bad bytes
    x = _field()
    comp = Compressor(CompressorSpec(eb=1e-3, verify="sample"))
    with perturb_quant_codes(n_calls=99, delta=16, frac=0.05):
        with pytest.raises(BoundViolationError) as ei:
            comp.compress(x)
    assert ei.value.repairs >= 1
    assert ei.value.max_err > ei.value.bound > 0


def test_verify_full_clean_field_telemetry():
    x = _field((24, 24))
    comp = Compressor(CompressorSpec(eb=1e-3, verify="full"))
    comp.compress(x)
    v = (comp.last_telemetry or {})["verify"]
    assert v["mode"] == "full"
    assert v["repairs"] == 0
    assert v["checked"] == x.size
    assert v["max_err"] <= v["bound"] * (1 + 1e-4) + 1e-12


def test_verify_sample_through_shard_frames():
    x = _field()
    comp = Compressor(CompressorSpec(eb=1e-3, verify="sample"))
    # one faulty predictor run: the first frame's initial encode is
    # perturbed, its repair re-encode (and every later frame) is clean
    with perturb_quant_codes(n_calls=1, delta=8, frac=0.02) as stats:
        buf = shard_compress(x, compressor=comp)
    y = np.asarray(shard_decompress(buf))
    rng = float(np.max(x)) - float(np.min(x))
    assert stats["perturbed"] > 0
    assert np.max(np.abs(x.astype(np.float64) - y.astype(np.float64))) <= 1e-3 * rng * (1 + 2e-4)
