"""Chaos suite: the fault injectors of :mod:`repro.testing.faults` driven
against the salvage decoder, the degraded consumers, the retry layer, and
device faults that must surface as errors.

Deterministic by construction — every random choice flows from
``fault_seed()`` (env ``REPRO_FAULTS``, default 20260808), so a CI chaos
lane can pin or sweep seeds and any failure replays exactly.
"""
import io
import pathlib
import zlib

import numpy as np
import pytest

from repro.core import (
    Compressor,
    CompressorSpec,
    ContainerError,
    FrameCRCError,
    FrameReader,
    FrameWriter,
    RetryPolicy,
    RetryingWriter,
    chunk_compress,
    retry_call,
    scan_frames,
)
from repro.core import frames as fr
from repro.testing import (
    FlakyFile,
    bit_flip,
    corrupt_frame,
    drop_frame,
    fault_rng,
    fault_seed,
    torn_tail,
    truncate_fraction,
)

DATA = pathlib.Path(__file__).parent / "data"
SPEC = CompressorSpec(eb=1e-2, pipeline="cr", autotune=False)


@pytest.fixture(scope="module")
def field():
    g = np.linspace(0, 4 * np.pi, 40)
    X, Y = np.meshgrid(g, np.linspace(0, 2 * np.pi, 64), indexing="ij")
    return (np.sin(X) * np.cos(Y)).astype(np.float32)


@pytest.fixture(scope="module")
def v3(field):
    return chunk_compress(field, n_chunks=4, spec=SPEC)


@pytest.fixture(scope="module")
def v3_sync(field):
    return chunk_compress(field, n_chunks=4, spec=SPEC, sync=True)


def _chunks(field, n=4):
    bounds = np.linspace(0, field.shape[0], n + 1).astype(int)
    return [field[a:b] for a, b in zip(bounds[:-1], bounds[1:])]


# ---------------------------------------------------------------- injectors


def test_bit_flip_flips_exactly_one_bit(v3):
    bad = bit_flip(v3, 100, bit=5)
    assert len(bad) == len(v3)
    diff = [i for i, (a, b) in enumerate(zip(v3, bad)) if a != b]
    assert diff == [100] and v3[100] ^ bad[100] == 1 << 5


def test_truncate_and_torn_tail(v3):
    t = truncate_fraction(v3, 0.5)
    assert len(t) == len(v3) // 2 and t == v3[: len(t)]
    torn = torn_tail(v3, 0.5, garbage=32, seed=7)
    assert len(torn) == len(v3) // 2 + 32 and torn[: len(v3) // 2] == v3[: len(v3) // 2]
    assert torn == torn_tail(v3, 0.5, garbage=32, seed=7)  # deterministic


def test_corrupt_and_drop_frame_target_the_right_record(v3, v3_sync):
    for buf in (v3, v3_sync):
        _, table = fr.frame_table(buf)
        bad = corrupt_frame(buf, 2)
        off = table[2][0]
        assert bad[off] != buf[off] and bad[:off] == buf[:off]
        dropped = drop_frame(buf, 1)
        assert len(dropped) < len(buf)


def test_fault_seed_env(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "1234")
    assert fault_seed() == 1234
    assert fault_rng().integers(0, 1 << 30) == fault_rng().integers(0, 1 << 30)
    monkeypatch.delenv("REPRO_FAULTS")
    assert fault_seed() == 20260808


def test_flaky_file_raises_then_counts():
    sink = io.BytesIO()
    f = FlakyFile(sink, fail_calls=(2, 4))
    f.write(b"a")  # call 1: ok
    with pytest.raises(OSError):
        f.write(b"b")  # call 2: injected fault, nothing written
    f.write(b"c")
    with pytest.raises(OSError):
        f.write(b"d")
    assert sink.getvalue() == b"ac" and f.faults == 2 and f.calls == 4


# ------------------------------------------------------------- salvage scan


@pytest.mark.parametrize("sync", [False, True])
def test_scan_frames_intact(v3, v3_sync, sync):
    buf = v3_sync if sync else v3
    good, report = scan_frames(buf)
    assert [i for i, _ in good] == [0, 1, 2, 3]
    assert report.ok and report.frames_ok == 4 and report.frames_damaged == 0


@pytest.mark.parametrize("sync", [False, True])
def test_scan_frames_single_corrupt_frame_keeps_others(v3, v3_sync, sync):
    buf = v3_sync if sync else v3
    _, table = fr.frame_table(buf)
    for victim in range(4):
        good, report = scan_frames(corrupt_frame(buf, victim))
        assert [i for i, _ in good] == [i for i in range(4) if i != victim]
        assert report.frames_damaged == 1 and not report.ok
        for i, payload in good:  # survivors are byte-identical
            off, size, _ = table[i]
            assert payload == bytes(buf[off : off + size])


@pytest.mark.parametrize("sync", [False, True])
def test_scan_frames_truncation_keeps_prefix(v3, v3_sync, sync):
    buf = v3_sync if sync else v3
    _, table = fr.frame_table(buf)
    cut = table[2][0] + 16  # mid-frame-2
    good, report = scan_frames(truncate_fraction(buf, cut / len(buf)))
    assert [i for i, _ in good] == [0, 1]
    assert report.truncated


def test_scan_frames_sync_resync_after_structural_damage(v3_sync):
    """Garbage splattered over a record boundary: sync markers recover the
    following frames with their *exact* sequence numbers."""
    _, table = fr.frame_table(v3_sync)
    bad = bytearray(v3_sync)
    start = table[1][0] - 12  # wreck frame 1's prefix itself
    rng = fault_rng()
    for i in range(start, start + 24):
        bad[i] = int(rng.integers(0, 256))
    good, report = scan_frames(bytes(bad))
    assert [i for i, _ in good] == [0, 2, 3]
    assert report.frames_damaged >= 1 and report.bytes_skipped > 0


def test_frame_reader_skip_mode(v3_sync):
    bad = corrupt_frame(v3_sync, 1)
    with FrameReader(io.BytesIO(bad)) as r:
        got = dict(r.iter_frames(on_error="skip"))
        assert sorted(got) == [0, 2, 3]
        assert not r.damage.ok and r.damage.frames_damaged == 1


def test_frame_reader_raise_mode(v3):
    bad = corrupt_frame(v3, 1)
    r = FrameReader(io.BytesIO(bad))
    with pytest.raises(FrameCRCError):
        list(r)


def test_frame_writer_abort_leaves_detectable_truncation(v3):
    sink = io.BytesIO()
    with pytest.raises(RuntimeError):
        with FrameWriter(sink, {"k": 1}) as w:
            w.write_frame(b"abc")
            raise RuntimeError("encode blew up")
    with pytest.raises(ContainerError):
        fr.frame_table(sink.getvalue())  # no trailer: honestly truncated
    good, report = scan_frames(sink.getvalue())
    assert [i for i, _ in good] == [0] and report.truncated


# ------------------------------------------------------- degraded consumers


def test_degraded_decompress_skip_and_fill(field, v3):
    comp = Compressor(SPEC)
    chunks = _chunks(field)
    ref = [comp.decompress(chunk_compress(field, n_chunks=4, spec=SPEC), frames=[i])
           for i in range(4)]
    bad = corrupt_frame(v3, 2)
    with pytest.raises((FrameCRCError, ContainerError)):
        comp.decompress(bad)
    skipped = comp.decompress(bad, on_error="skip")
    assert skipped.shape[0] == field.shape[0] - chunks[2].shape[0]
    assert comp.last_damage["chunks_ok"] == [True, True, False, True]
    filled = comp.decompress(bad, on_error="fill", fill_value=-1.0)
    assert filled.shape == field.shape
    a = sum(c.shape[0] for c in chunks[:2])
    assert np.all(filled[a : a + chunks[2].shape[0]] == -1.0)
    np.testing.assert_array_equal(filled[:a], np.concatenate(ref[:2]))


def test_degraded_decompress_all_frames_lost_raises(v3):
    comp = Compressor(SPEC)
    bad = v3
    for i in range(4):
        bad = corrupt_frame(bad, i)
    with pytest.raises(ContainerError):
        comp.decompress(bad, on_error="skip")


def test_inspect_reports_damage(v3):
    bad = corrupt_frame(v3, 1)
    info = Compressor.inspect(bad)
    assert info["frame_crc_ok"] == [True, False, True, True]
    assert not info["damage"].ok


def test_inspect_salvages_truncated_container(v3):
    _, table = fr.frame_table(v3)
    info = Compressor.inspect(truncate_fraction(v3, (table[2][0] + 8) / len(v3)))
    assert info["frame_indices"] == [0, 1] and info["damage"].truncated


# --------------------------------------------------------- golden fixtures


def test_golden_bitflip_salvage(field):
    """Committed bit-flipped archive: frame 1 is lost, every other chunk
    decodes byte-identically to the intact golden decode."""
    buf = (DATA / "golden_v3_bitflip.bin").read_bytes()
    ref = np.load(DATA / "golden_decoded_v3.npy")
    comp = Compressor(SPEC)
    with pytest.raises((FrameCRCError, ContainerError)):
        comp.decompress(buf)
    out = comp.decompress(buf, on_error="fill", fill_value=np.nan)
    assert out.shape == ref.shape
    assert comp.last_damage["chunks_ok"] == [True, False, True, True]
    sizes = Compressor.inspect(buf)["chunk_sizes"]
    lo, hi = sizes[0], sizes[0] + sizes[1]
    assert np.isnan(out[lo:hi]).all()
    mask = np.ones(ref.shape[0], bool)
    mask[lo:hi] = False
    np.testing.assert_array_equal(out[mask], ref[mask])


def test_golden_trunc_salvage():
    buf = (DATA / "golden_v3_trunc.bin").read_bytes()
    ref = np.load(DATA / "golden_decoded_v3.npy")
    comp = Compressor(SPEC)
    out = comp.decompress(buf, on_error="skip")
    assert comp.last_damage["chunks_ok"] == [True, True, False, False]
    sizes = comp.inspect((DATA / "golden_v3.bin").read_bytes())["chunk_sizes"]
    np.testing.assert_array_equal(out, ref[: sizes[0] + sizes[1]])


def test_golden_torn_salvage():
    buf = (DATA / "golden_v3_torn.bin").read_bytes()
    ref = np.load(DATA / "golden_decoded_v3.npy")
    comp = Compressor(SPEC)
    out = comp.decompress(buf, on_error="skip")
    assert comp.last_damage["chunks_ok"] == [True, True, True, False]
    keep = out.shape[0]
    np.testing.assert_array_equal(out, ref[:keep])


def test_golden_v3_still_reads_bytes_for_byte():
    """The intact golden archive predates sync markers: it must keep
    decoding to the committed reconstruction, unchanged."""
    buf = (DATA / "golden_v3.bin").read_bytes()
    ref = np.load(DATA / "golden_decoded_v3.npy")
    np.testing.assert_array_equal(Compressor(SPEC).decompress(buf), ref)


# ------------------------------------------------------------ retry + I/O


def test_retry_call_backs_off_then_succeeds():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    out = retry_call(flaky, policy=RetryPolicy(attempts=3, jitter=0.0),
                     sleep=sleeps.append, seed=0)
    assert out == "ok" and calls["n"] == 3
    assert sleeps == [0.05, 0.1]  # base * 2**(attempt-1), no jitter


def test_retry_call_exhausts():
    with pytest.raises(OSError):
        retry_call(lambda: (_ for _ in ()).throw(OSError("down")),
                   policy=RetryPolicy(attempts=2), sleep=lambda s: None)


def test_retrying_writer_survives_flaky_sink(v3):
    sink = io.BytesIO()
    flaky = FlakyFile(sink, fail_calls=(1, 4))
    w = RetryingWriter(flaky, policy=RetryPolicy(attempts=3, jitter=0.0), sleep=lambda s: None)
    for i in range(0, len(v3), 1000):
        w.write(v3[i : i + 1000])
    assert sink.getvalue() == v3 and w.retries == 2


def test_chunk_compress_through_flaky_sink_retries(field):
    """End-to-end: transient write faults under the frame writer cost
    retries, not bytes — the container comes out byte-identical."""
    ref = chunk_compress(field, n_chunks=4, spec=SPEC)
    sink = io.BytesIO()
    w = RetryingWriter(FlakyFile(sink, fail_calls=(2, 5)),
                       policy=RetryPolicy(attempts=3, jitter=0.0), sleep=lambda s: None)
    chunk_compress(field, n_chunks=4, spec=SPEC, out=w)
    assert sink.getvalue() == ref and w.retries == 2


def test_encode_tensor_to_retries_transient_oserror(monkeypatch):
    from repro.checkpoint.codec import decode_tensor, encode_tensor_to

    monkeypatch.setenv("REPRO_IO_RETRIES", "4")
    x = np.linspace(0, 1, 100 * 64, dtype=np.float32).reshape(100, 64)
    sink = io.BytesIO()
    meta = encode_tensor_to(FlakyFile(sink, fail_calls=(1, 3)), x, eb=1e-3)
    assert meta["io_retries"] == 2
    assert meta["crc32"] == (zlib.crc32(sink.getvalue()) & 0xFFFFFFFF)
    out = decode_tensor(sink.getvalue(), meta)
    rng = x.max() - x.min()
    assert np.abs(out - x).max() <= 1e-3 * rng * (1 + 1e-5)


# ------------------------------------------------ device faults surface


def test_device_encode_failure_falls_back_bit_identical(field, monkeypatch):
    """A device-engine fault raises to the caller; no silent host retry."""
    comp = Compressor(CompressorSpec(eb=1e-2, pipeline="cr", autotune=False, engine="device"))
    ref = comp.compress(field)
    assert comp.last_telemetry["engine"] == "device"

    from repro.core.lossless import pipelines as pp

    real_encode = pp.encode

    def sabotaged(seq, *a, **kw):
        if not isinstance(seq, np.ndarray):
            raise RuntimeError("injected device-engine failure")
        return real_encode(seq, *a, **kw)

    monkeypatch.setattr(pp, "encode", sabotaged)
    comp2 = Compressor(CompressorSpec(eb=1e-2, pipeline="cr", autotune=False, engine="device"))
    with pytest.raises(RuntimeError, match="injected device-engine failure"):
        comp2.compress(field)
    # the host engine never touches the sabotaged device path
    host = Compressor(CompressorSpec(eb=1e-2, pipeline="cr", autotune=False, engine="numpy"))
    assert host.compress(field) == ref  # engine bit-identity contract


def test_telemetry_resets_between_calls(field):
    comp = Compressor(SPEC)
    comp.compress(field)
    first = comp.last_telemetry
    comp.compress(field)
    assert comp.last_telemetry is not first  # fresh record per call


# --------------------------------------------------- tier-2 property sweep


@pytest.mark.tier2
def test_single_frame_corruption_never_loses_other_frames(field):
    """Property: whatever single frame a random bit flip lands in, every
    *other* frame survives salvage byte-identically, in both layouts."""
    hypothesis = pytest.importorskip("hypothesis", reason="optional dev dependency")
    given, settings, st = hypothesis.given, hypothesis.settings, hypothesis.strategies

    bufs = {s: chunk_compress(field, n_chunks=5, spec=SPEC, sync=s) for s in (False, True)}
    tables = {s: fr.frame_table(b)[1] for s, b in bufs.items()}

    @settings(max_examples=60, deadline=None)
    @given(sync=st.booleans(), victim=st.integers(0, 4),
           rel=st.floats(0, 1, exclude_max=True), bit=st.integers(0, 7))
    def prop(sync, victim, rel, bit):
        buf, table = bufs[sync], tables[sync]
        off, size, _ = table[victim]
        bad = bit_flip(buf, off + int(rel * size), bit=bit)
        good, report = scan_frames(bad)
        got = dict(good)
        for i in range(5):
            if i == victim:
                continue
            o, s_, _ = table[i]
            assert got[i] == bytes(buf[o : o + s_])
        assert report.frames_damaged == 1 and report.frames_ok == 4

    prop()


@pytest.mark.tier2
def test_random_bitflip_sweep_runs_without_hypothesis(field):
    """Same property as above, driven by the pinned chaos seed — runs in
    environments without hypothesis (the CI chaos lane sweeps the seed)."""
    bufs = {s: chunk_compress(field, n_chunks=5, spec=SPEC, sync=s) for s in (False, True)}
    tables = {s: fr.frame_table(b)[1] for s, b in bufs.items()}
    rng = fault_rng()
    for _ in range(40):
        sync = bool(rng.integers(0, 2))
        buf, table = bufs[sync], tables[sync]
        victim = int(rng.integers(0, 5))
        off, size, _ = table[victim]
        bad = bit_flip(buf, off + int(rng.integers(0, size)), bit=int(rng.integers(0, 8)))
        good, report = scan_frames(bad)
        got = dict(good)
        for i in range(5):
            if i == victim:
                continue
            o, s_, _ = table[i]
            assert got[i] == bytes(buf[o : o + s_]), (sync, victim, i)
        assert report.frames_damaged == 1 and report.frames_ok == 4


def test_shard_decompress_degraded_parallel(field):
    from repro.core import shard_decompress

    buf = chunk_compress(field, n_chunks=4, spec=SPEC)
    comp = Compressor(SPEC)
    bad = corrupt_frame(buf, 3)
    out = shard_decompress(bad, workers=4, on_error="fill", fill_value=0.0, compressor=comp)
    assert out.shape == field.shape
    assert comp.last_damage["chunks_ok"] == [True, True, True, False]
    with pytest.raises((FrameCRCError, ContainerError)):
        shard_decompress(bad, workers=4, compressor=comp)
