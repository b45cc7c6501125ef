"""Device encoding engine: bit-identity with the numpy reference stages.

The engine's contract (repro.core.lossless.engine) is that every
``encode_device`` twin produces a payload byte-for-byte equal to the numpy
encoder's, so device-encoded sections drop into existing containers and a
sharded writer stays interchangeable with a single-host one. These tests
pin that contract at every level: stage, pipeline stream, orchestrator
choice, and full compressor container.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.lossless import bitshuffle as bs  # noqa: E402
from repro.core.lossless import engine as eng  # noqa: E402
from repro.core.lossless import huffman as hf  # noqa: E402
from repro.core.lossless import orchestrate as orc  # noqa: E402
from repro.core.lossless import pipelines as pp  # noqa: E402
from repro.core.lossless import rre, tcms  # noqa: E402
from repro.core.lossless.stages import registered_stages  # noqa: E402


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


@pytest.mark.parametrize("name,data", STREAMS)
def test_hf_device_bit_identical(name, data):
    payload, hdr = hf.encode(data)
    pdev, hdev = eng.hf_encode_device(jnp.asarray(data))
    assert hdev == hdr, name
    assert np.asarray(pdev).tobytes() == payload, name


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("name,data", STREAMS)
def test_rre_rze_device_bit_identical(k, name, data):
    d = jnp.asarray(data)
    payload, hdr = rre.rre_encode(data, k)
    pdev, hdev = eng.rre_encode_device(d, k)
    assert (hdev, np.asarray(pdev).tobytes()) == (hdr, payload), name
    payload, hdr = rre.rze_encode(data, k)
    pdev, hdev = eng.rze_encode_device(d, k)
    assert (hdev, np.asarray(pdev).tobytes()) == (hdr, payload), name


def _sparse(nbytes: int, seed: int) -> np.ndarray:
    """Zeros with random bytes at heavy-tailed gaps, so that every bitmap
    level holds zero and non-zero bytes."""
    rng = np.random.default_rng(seed)
    out = np.zeros(nbytes, np.uint8)
    pos = np.cumsum((rng.pareto(0.7, nbytes // 16 + 1) * 3).astype(np.int64) + 1)
    pos = pos[pos < nbytes]
    out[pos] = rng.integers(1, 256, pos.size)
    return out


# bitmap level i holds ceil(nsym / 8^(i+1)) bytes and recurses while that
# is over rre._BITMAP_FLOOR (64): one below, at and one above the floor for
# the top level of 0..3 recursed levels
FLOOR_NSYM = {f"L{lv}-top{top}": top * 8 ** (lv + 1) - (top == 65) * (8 ** (lv + 1) - 1)
              for lv in range(4) for top in (63, 64, 65)}


def _size_stream(name: str, k: int) -> np.ndarray:
    rng = np.random.default_rng(k)
    if name in FLOOR_NSYM:
        return _sparse(FLOOR_NSYM[name] * k, FLOOR_NSYM[name])
    return {
        "empty": lambda: np.zeros(0, np.uint8),
        "one-byte": lambda: np.array([200], np.uint8),
        "constant": lambda: np.full(3000, 7, np.uint8),
        "random": lambda: rng.integers(0, 256, 5000, dtype=np.uint8),
        "runs": lambda: np.repeat(rng.integers(0, 4, 60, dtype=np.uint8), 101),
        "padded": lambda: _sparse(5000 * k + 1, k),  # n % k == 1 for k > 1
    }[name]()


@pytest.mark.parametrize("name", ["empty", "one-byte", "constant", "random", "runs", "padded", *FLOOR_NSYM])
@pytest.mark.parametrize("zero_mode", [False, True])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_rr_device_size_is_exact(k, zero_mode, name):
    """The twin sizes its payload from the flags alone, exactly: with the
    store-through limit at the reference payload's size it returns no
    payload (``pipelines.encode`` stores through), one byte above it it
    returns the reference payload."""
    data = _size_stream(name, k)
    ref, hdr = (rre.rze_encode if zero_mode else rre.rre_encode)(data, k)
    twin = eng.rze_encode_device if zero_mode else eng.rre_encode_device
    d = jnp.asarray(data)
    seen = []
    got, h = twin(d, k, limit=lambda h: seen.append(h) or len(ref))
    assert got is None and h == hdr == seen[0]
    got, h = twin(d, k, limit=lambda h: len(ref) + 1)
    assert (h, np.asarray(got).tobytes()) == (hdr, ref)


@pytest.mark.parametrize("pipe", ["tp", "fz", "lvl"])
def test_store_through_is_decided_from_the_size(pipe):
    """A random stream makes rre/rze expand: the pipeline stores them
    through from their size alone. A run-heavy one compacts: no stage is
    decided early. Either way the bytes are the numpy path's."""
    from repro.core import spans

    rng = np.random.default_rng(5)
    streams = {"random": rng.integers(0, 256, 200_000, dtype=np.uint8),
               "runs": np.repeat(rng.integers(0, 4, 20, dtype=np.uint8), 10_000)}
    early = {}
    for name, data in streams.items():
        with spans.call("compress") as rec:
            buf = pp.encode(jnp.asarray(data), pipe)
        assert buf == pp.encode(data, pipe), (pipe, name)
        assert np.array_equal(pp.decode(buf), data), (pipe, name)
        early[name] = rec.counters.get("store_through_early", 0)
        assert rec.counters.get("store_through", 0) >= early[name]
    assert early["random"] >= 1 and early["runs"] == 0, early


@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("name,data", STREAMS)
def test_tcms_device_bit_identical(k, name, data):
    payload, hdr = tcms.tcms_encode(data, k)
    pdev, hdev = eng.tcms_encode_device(jnp.asarray(data), k)
    assert (hdev, np.asarray(pdev).tobytes()) == (hdr, payload), name


@pytest.mark.parametrize("name,data", STREAMS)
def test_bit1_device_bit_identical(name, data):
    payload, hdr = bs.bitshuffle_encode(data)
    pdev, hdev = eng.bit1_encode_device(jnp.asarray(data))
    assert (hdev, np.asarray(pdev).tobytes()) == (hdr, payload), name


def test_hf_device_seam_skip_fuzz():
    """Chunk seams are byte- (not word-) aligned: the gap between pair
    starts can hop a whole 32-bit word. Random multi-chunk streams across
    several symbol laws exercise the seam-repair path."""
    rng = np.random.default_rng(7)
    for t in range(60):
        n = int(rng.integers(1, 6 * hf.CHUNK))
        data = np.clip(
            np.round(rng.laplace(rng.integers(0, 256), rng.choice([0.5, 2.0, 8.0, 40.0]), n)),
            0, 255,
        ).astype(np.uint8)
        ref, _ = hf.encode(data)
        got, _ = eng.hf_encode_device(jnp.asarray(data))
        assert np.asarray(got).tobytes() == ref, (t, n)


def test_hf_device_multi_slab_bit_identical(monkeypatch):
    """Streams beyond _PAR_SLAB split into async-dispatched slabs whose
    payloads must concatenate byte-exactly. Shrinking the slab size forces
    several slabs (plus a partial tail chunk) without a huge stream."""
    rng = np.random.default_rng(11)
    data = np.clip(np.round(rng.laplace(128.0, 8.0, 5 * (1 << 16) + 777)), 0, 255).astype(np.uint8)
    ref, ref_hdr = hf.encode(data)
    monkeypatch.setattr(eng, "_PAR_SLAB", 1 << 16)  # 5 slabs + tail
    got, hdr = eng.hf_encode_device(jnp.asarray(data))
    assert hdr == ref_hdr
    assert np.asarray(got).tobytes() == ref


def test_every_builtin_stage_has_device_twin_except_zstd():
    stages = registered_stages()
    for name, st in stages.items():
        if name == "zstd":
            assert st.encode_device is None
        else:
            assert st.encode_device is not None, name


@pytest.mark.parametrize("pipe", sorted(pp.registered_pipelines()))
@pytest.mark.parametrize("name,data", STREAMS[:6])
def test_pipeline_device_stream_bit_identical(pipe, name, data):
    """Device-resident pipeline encode == host encode, for every registered
    pipeline (crz exercises the host fallback for the zstd stage)."""
    host = pp.encode(data, pipe)
    dev = pp.encode(jnp.asarray(data), pipe)
    assert dev == host, (pipe, name)
    assert np.array_equal(pp.decode(dev), data), (pipe, name)


def test_stream_stats_device_matches_host():
    rng = np.random.default_rng(3)
    data = np.clip(np.round(rng.laplace(128, 6, 200_000)), 0, 255).astype(np.uint8)
    sh = orc.stream_stats(orc.sample_stream(data), n_total=data.size)
    sd = orc.stream_stats(orc.sample_stream(jnp.asarray(data)), n_total=data.size)
    assert sh == sd  # exact equality: integer histograms, exact ratios


def test_encode_auto_device_matches_host():
    rng = np.random.default_rng(4)
    for data in (
        np.clip(np.round(rng.laplace(128, 8, 150_000)), 0, 255).astype(np.uint8),
        np.repeat(rng.integers(126, 131, 3000, dtype=np.uint8), 64),
        np.where(rng.random(120_000) < 0.02, rng.integers(0, 256, 120_000), 128).astype(np.uint8),
    ):
        bh, rh = orc.encode_auto(data)
        bd, rd = orc.encode_auto(jnp.asarray(data))
        assert bh == bd
        assert rh == rd  # same stats, same estimates, same chosen pipeline


def test_compressor_engine_paths_bit_identical(smooth3d):
    from repro.core import Compressor, CompressorSpec

    for pipeline in ("cr", "auto"):
        specs = [CompressorSpec(eb=1e-3, pipeline=pipeline, engine=e)
                 for e in ("numpy", "device", "auto")]
        bufs = [Compressor(s).compress(smooth3d) for s in specs]
        assert bufs[0] == bufs[1] == bufs[2], pipeline
        out = Compressor(specs[0]).decompress(bufs[1])
        rng = float(smooth3d.max() - smooth3d.min())
        assert np.abs(out - smooth3d).max() <= 1e-3 * rng * (1 + 1e-5) + 1e-9


def test_compressor_engine_validation():
    from repro.core import CompressorSpec

    with pytest.raises(ValueError, match="unknown engine"):
        CompressorSpec(engine="gpu")


def test_hf_nworkers_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_HF_WORKERS", "3")
    assert hf._nworkers() == 3
    monkeypatch.setenv("REPRO_HF_WORKERS", "not-a-number")
    assert hf._nworkers() >= 1
    monkeypatch.setenv("REPRO_HF_WORKERS", "-2")
    assert hf._nworkers() >= 1
    monkeypatch.delenv("REPRO_HF_WORKERS")
    assert hf._nworkers() >= 1
