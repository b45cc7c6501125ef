"""Device decode engine: bit-identity with the numpy reference stages.

PR 5's engine contract covered the encode direction; these tests pin the
symmetric read path: every ``decode_device`` twin reproduces the numpy
decoder's bytes exactly — per stage, per pipeline stream (v2 and legacy
v1 framing), and through the full compressor (v1/v2/v3 containers and
the committed golden fixtures) — and a device decode failure falls back
to the numpy path bit-identically, observable only in telemetry.
"""
import pathlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import Compressor, CompressorSpec  # noqa: E402
from repro.core.lossless import bitshuffle as bs  # noqa: E402
from repro.core.lossless import engine as eng  # noqa: E402
from repro.core.lossless import huffman as hf  # noqa: E402
from repro.core.lossless import pipelines as pp  # noqa: E402
from repro.core.lossless import rre, tcms  # noqa: E402
from repro.core.lossless.stages import get_stage, registered_stages  # noqa: E402

_GOLDEN = pathlib.Path(__file__).parent / "data"


def _streams():
    rng = np.random.default_rng(0)
    yield "random", rng.integers(0, 256, 5000, dtype=np.uint8)
    yield "skewed", np.minimum(rng.zipf(1.5, 5000), 255).astype(np.uint8)
    yield "runs", np.repeat(rng.integers(0, 4, 100, dtype=np.uint8), 57)[:5000]
    yield "zeros", np.zeros(4096, np.uint8)
    yield "tiny", np.array([128], np.uint8)
    yield "empty", np.zeros(0, np.uint8)
    yield "single-symbol", np.full(3000, 7, np.uint8)
    yield "chunk", rng.integers(0, 256, hf.CHUNK, dtype=np.uint8)
    yield "chunk-1", rng.integers(0, 256, hf.CHUNK - 1, dtype=np.uint8)
    yield "chunk+1", rng.integers(0, 256, hf.CHUNK + 1, dtype=np.uint8)
    yield "deepskew", np.clip(rng.normal(128, 2.5, 1 << 17), 0, 255).astype(np.uint8)


STREAMS = list(_streams())


# ------------------------------------------------------------ stage twins
@pytest.mark.parametrize("name,data", STREAMS)
def test_hf_decode_device_bit_identical(name, data):
    payload, hdr = hf.encode(data)
    ref = hf.decode(payload, hdr)
    got = eng.hf_decode_device(payload, hdr)
    assert np.array_equal(np.asarray(got), ref), name
    # legacy stream without the offset table: host-fallback, same bytes
    legacy = {k: v for k, v in hdr.items() if k != "offs"}
    got = eng.hf_decode_device(payload, legacy)
    assert np.array_equal(np.asarray(got), ref), name


def test_hf_offset_table_matches_device_encoder():
    """Both encoders must emit the identical versioned header (the engine
    contract extends to the "offs" extension: header dict equality)."""
    rng = np.random.default_rng(5)
    data = np.clip(np.round(rng.laplace(128, 6, 3 * hf.CHUNK + 100)), 0, 255).astype(np.uint8)
    _, hdr = hf.encode(data)
    _, hdev = eng.hf_encode_device(jnp.asarray(data))
    assert "offs" in hdr and hdev == hdr


def test_hf_header_pack_roundtrip_versioned_and_legacy():
    st = get_stage("hf")
    rng = np.random.default_rng(6)
    data = np.clip(np.round(rng.laplace(128, 4, 2 * hf.CHUNK + 7)), 0, 255).astype(np.uint8)
    _, hdr = hf.encode(data)
    assert st.unpack_header(st.pack_header(hdr)) == hdr
    # the bare 8-byte form predates the table and must keep parsing
    import struct

    assert st.unpack_header(struct.pack("<Q", 12345)) == {"n": 12345}
    legacy = {"n": hdr["n"]}
    assert len(st.pack_header(legacy)) == 8
    assert st.unpack_header(st.pack_header(legacy)) == legacy


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("name,data", STREAMS)
def test_rre_rze_decode_device_bit_identical(k, name, data):
    payload, hdr = rre.rre_encode(data, k)
    ref = rre.rre_decode(payload, hdr)
    assert np.array_equal(np.asarray(eng.rre_decode_device(payload, hdr)), ref), name
    payload, hdr = rre.rze_encode(data, k)
    ref = rre.rze_decode(payload, hdr)
    assert np.array_equal(np.asarray(eng.rze_decode_device(payload, hdr)), ref), name


@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("name,data", STREAMS)
def test_tcms_decode_device_bit_identical(k, name, data):
    payload, hdr = tcms.tcms_encode(data, k)
    ref = tcms.tcms_decode(payload, hdr)
    assert np.array_equal(np.asarray(eng.tcms_decode_device(payload, hdr)), ref), name


@pytest.mark.parametrize("name,data", STREAMS)
def test_bit1_decode_device_bit_identical(name, data):
    payload, hdr = bs.bitshuffle_encode(data)
    ref = bs.bitshuffle_decode(payload, hdr)
    assert np.array_equal(np.asarray(eng.bit1_decode_device(payload, hdr)), ref), name


@pytest.mark.parametrize("name,data", STREAMS)
def test_encode_device_decode_device_roundtrip(name, data):
    """Full device roundtrip, device payload in, device stream out: the
    decode twin accepts the encode twin's device array directly."""
    d = jnp.asarray(data)
    payload, hdr = eng.hf_encode_device(d)
    assert np.array_equal(np.asarray(eng.hf_decode_device(payload, hdr)), data), name
    payload, hdr = eng.rre_encode_device(d, 4)
    assert np.array_equal(np.asarray(eng.rre_decode_device(payload, hdr)), data), name
    payload, hdr = eng.tcms_encode_device(d, 8)
    assert np.array_equal(np.asarray(eng.tcms_decode_device(payload, hdr)), data), name
    payload, hdr = eng.bit1_encode_device(d)
    assert np.array_equal(np.asarray(eng.bit1_decode_device(payload, hdr)), data), name


def test_hf_decode_device_fuzz():
    """Random multi-chunk streams across symbol laws: the device decoder's
    per-chunk parallel entry points must agree with the sequential
    reference at every chunk seam."""
    rng = np.random.default_rng(9)
    for t in range(40):
        n = int(rng.integers(1, 6 * hf.CHUNK))
        data = np.clip(
            np.round(rng.laplace(rng.integers(0, 256), rng.choice([0.5, 2.0, 8.0, 40.0]), n)),
            0, 255,
        ).astype(np.uint8)
        payload, hdr = hf.encode(data)
        assert np.array_equal(np.asarray(eng.hf_decode_device(payload, hdr)), data), (t, n)


def test_every_builtin_stage_has_decode_twin_except_zstd():
    for name, st in registered_stages().items():
        if name == "zstd":
            assert st.decode_device is None
        else:
            assert st.decode_device is not None, name


# ------------------------------------------------------- pipeline streams
@pytest.mark.parametrize("pipe", sorted(pp.registered_pipelines()))
@pytest.mark.parametrize("name,data", STREAMS[:6])
def test_pipeline_device_decode_bit_identical(pipe, name, data):
    buf = pp.encode(data, pipe)
    out = pp.decode(buf, device=True)
    assert not isinstance(out, np.ndarray)  # device-resident result
    assert np.array_equal(np.asarray(out), data), (pipe, name)


@pytest.mark.parametrize("pipe", ["cr", "tp", "fzh"])
def test_pipeline_device_decode_legacy_v1_stream(pipe):
    """Pre-registry JSON streams lack binary header extensions: the device
    path decodes them through the host reference stages, then uploads."""
    rng = np.random.default_rng(2)
    data = np.clip(np.round(rng.laplace(128, 5, 40_000)), 0, 255).astype(np.uint8)
    buf = pp.encode_v1(data, pipe)
    assert np.array_equal(pp.decode(buf), data)
    assert np.array_equal(np.asarray(pp.decode(buf, device=True)), data)


def test_pipeline_decode_accepts_memoryview_and_ndarray():
    rng = np.random.default_rng(3)
    data = np.clip(np.round(rng.laplace(128, 5, 30_000)), 0, 255).astype(np.uint8)
    buf = pp.encode(data, "cr")
    for view in (memoryview(buf), bytearray(buf), np.frombuffer(buf, np.uint8)):
        assert np.array_equal(pp.decode(view), data), type(view).__name__
    assert np.array_equal(np.asarray(pp.decode(memoryview(buf), device=True)), data)


# ----------------------------------------------------------- compressor
def test_compressor_decode_engines_bit_identical(smooth3d):
    for predictor in ("interp", "lorenzo"):
        spec = CompressorSpec(eb=1e-3, pipeline="cr", autotune=False, predictor=predictor)
        buf = Compressor(spec).compress(smooth3d)
        ref = Compressor(spec).decompress(buf)
        dev = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False,
                                        predictor=predictor, engine="device"))
        got = dev.decompress(buf)
        assert isinstance(got, np.ndarray) and np.array_equal(got, ref), predictor
        assert dev.last_telemetry["decode"]["engine"] == "device", predictor


def test_compressor_out_device_returns_device_array(smooth3d):
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False))
    buf = comp.compress(smooth3d)
    ref = comp.decompress(buf)
    got = comp.decompress(buf, out="device")
    assert not isinstance(got, np.ndarray)
    assert np.array_equal(np.asarray(got), ref)
    # engine="numpy" still honours out= (host decode, then upload)
    host = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False, engine="numpy"))
    got = host.decompress(buf, out="device")
    assert not isinstance(got, np.ndarray)
    assert np.array_equal(np.asarray(got), ref)
    assert host.last_telemetry["decode"]["engine"] == "numpy"
    with pytest.raises(ValueError, match="out must be"):
        comp.decompress(buf, out="tpu")


def test_decode_telemetry_recorded(smooth3d):
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False))
    buf = comp.compress(smooth3d)
    comp.decompress(buf)
    td = comp.last_telemetry["decode"]
    assert td["engine"] == "numpy" and td["out"] == "numpy"
    assert td["mbps"] > 0 and td["seconds"] > 0 and td["bytes"] == smooth3d.nbytes
    comp.decompress(buf, out="device")
    td = comp.last_telemetry["decode"]
    assert td["engine"] == "device" and td["out"] == "device"


@pytest.mark.parametrize("version", [1, 2, 3])
def test_golden_containers_decode_device_byte_for_byte(version):
    """The committed cross-version blobs must decode identically through
    the device engine — fallbacks allowed (v1 streams host-decode), byte
    differences not."""
    blob = (_GOLDEN / f"golden_v{version}.bin").read_bytes()
    expected = np.load(_GOLDEN / ("golden_decoded_v3.npy" if version == 3 else "golden_decoded.npy"))
    comp = Compressor(CompressorSpec(eb=1e-2, pipeline="cr", autotune=False, engine="device"))
    out = comp.decompress(blob)
    assert out.dtype == np.float32 and np.array_equal(out, expected)
    out = comp.decompress(blob, out="device")
    assert np.array_equal(np.asarray(out), expected)


def test_v3_device_decode_and_frame_selection(smooth3d):
    from repro.core.distributed import chunk_compress, shard_decompress

    x = np.stack([smooth3d * (1 + 0.1 * i) for i in range(3)]).astype(np.float32)
    spec = CompressorSpec(eb=1e-3, pipeline="cr", autotune=False)
    buf = chunk_compress(x, n_chunks=3, spec=spec)
    comp = Compressor(spec)
    ref = comp.decompress(buf)
    got = comp.decompress(buf, out="device")
    assert not isinstance(got, np.ndarray) and np.array_equal(np.asarray(got), ref)
    sub = comp.decompress(buf, frames=[2, 0], out="device")
    assert np.array_equal(np.asarray(sub), np.concatenate([ref[2:3], ref[0:1]]))
    # parallel frame decode straight onto device
    for workers in (1, 2):
        sd = shard_decompress(buf, workers=workers, out="device")
        assert not isinstance(sd, np.ndarray) and np.array_equal(np.asarray(sd), ref)


def test_device_decode_failure_falls_back_bit_identical(smooth3d, monkeypatch):
    """Chaos: a device decode fault raises to the caller (no silent host
    retry), while the host engine still decodes the same container."""
    spec = CompressorSpec(eb=1e-3, pipeline="cr", autotune=False, engine="device")
    buf = Compressor(spec).compress(smooth3d)
    ref = Compressor(spec).decompress(buf)

    real_decode = pp.decode

    def sabotaged(buf_, device=False):
        if device:
            raise RuntimeError("injected device decode fault")
        return real_decode(buf_)

    monkeypatch.setattr(pp, "decode", sabotaged)
    # compressor.py binds `pipelines` as a module, so patching pp.decode
    # is visible at the call site
    comp = Compressor(spec)
    with pytest.raises(RuntimeError, match="injected device decode fault"):
        comp.decompress(buf)
    host = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False, engine="numpy"))
    assert np.array_equal(host.decompress(buf), ref)


def test_decode_workers_env_override(monkeypatch):
    from repro.core import distributed as dist

    monkeypatch.setenv("REPRO_DECODE_WORKERS", "3")
    assert dist._decode_workers() == 3
    monkeypatch.setenv("REPRO_DECODE_WORKERS", "not-a-number")
    assert dist._decode_workers() == 1
    monkeypatch.setenv("REPRO_DECODE_WORKERS", "-2")
    assert dist._decode_workers() == 1
    monkeypatch.delenv("REPRO_DECODE_WORKERS")
    assert dist._decode_workers() == 1


def test_shard_decompress_default_workers_from_env(smooth3d, monkeypatch):
    from repro.core.distributed import chunk_compress, shard_decompress

    x = np.stack([smooth3d, smooth3d * 1.1]).astype(np.float32)
    spec = CompressorSpec(eb=1e-3, pipeline="cr", autotune=False)
    buf = chunk_compress(x, n_chunks=2, spec=spec)
    ref = shard_decompress(buf, workers=1)
    monkeypatch.setenv("REPRO_DECODE_WORKERS", "2")
    assert np.array_equal(shard_decompress(buf), ref)  # workers=None -> env


def test_frame_reader_zero_copy_memoryview(smooth3d):
    """read_frame hands payloads through as CRC-checked memoryviews; the
    decode stack accepts them without an owning copy."""
    import repro.core.frames as fr
    from repro.core.distributed import chunk_compress

    spec = CompressorSpec(eb=1e-3, pipeline="cr", autotune=False)
    x = np.stack([smooth3d, smooth3d * 1.05]).astype(np.float32)
    buf = chunk_compress(x, n_chunks=2, spec=spec)
    header, table = fr.frame_table(buf)
    frame = fr.read_frame(buf, table[0])
    assert isinstance(frame, memoryview)
    comp = Compressor(spec)
    part = comp.decompress(frame)
    assert part.shape[0] == header["chunk_sizes"][0]
