"""The compressor's own spans and counters (``repro.core.spans``).

Spans nest and give self times; each thread keeps its own record, also
when many threads share one compressor; the process log is bounded; a
compile lands in the innermost open span; the host<->device counters
equal the array bytes that crossed, as JAX's transfer guard (host to
device) and the arrays' buffer protocol (device to host) see them; and
recording changes no container byte.
"""
import contextlib
import hashlib
import os
import re
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Compressor, CompressorSpec, spans


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(spans, "time", c)
    return c


def field(shape, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    for ax in range(3):
        x = np.cumsum(x, axis=ax)
    return x.astype(np.float32)


def test_spans_nest_and_give_self_time(clock):
    with spans.call("compress") as rec:
        clock.now = 1.0
        with spans.span("a") as a:
            clock.now = 2.0
            with spans.span("b") as b:
                clock.now = 3.0
            clock.now = 5.0
        clock.now = 6.0
        with spans.span("a"):
            clock.now = 8.0
        clock.now = 10.0
    assert isinstance(rec, spans.Record) and rec.seconds == 10.0
    assert [(s.name, s.t0, s.t1, s.parent) for s in rec.spans] == [
        ("a", 1.0, 5.0, -1), ("b", 2.0, 3.0, 0), ("a", 6.0, 8.0, -1)]
    assert (a.seconds, b.seconds) == (4.0, 1.0)
    assert rec.self_seconds() == {"compress": 4.0, "a": 5.0, "b": 1.0}
    assert spans.calls()[-1] is rec


def test_a_call_inside_a_call_is_a_span(clock):
    n = len(spans.calls())
    with spans.call("compress") as rec:
        with spans.span("compress.verify"):
            clock.now = 1.0
            with spans.call("decompress") as inner:
                clock.now = 3.0
                spans.count("in_bytes", 7)
            clock.now = 4.0
        clock.now = 5.0
    assert isinstance(inner, spans.Span) and inner.name == "decompress" and inner.parent == 0
    assert inner.seconds == 2.0 and rec.counters["in_bytes"] == 7
    assert rec.self_seconds() == {"compress": 1.0, "compress.verify": 2.0, "decompress": 2.0}
    assert spans.calls()[-1] is rec and len(spans.calls()) in (n + 1, spans.LOG_SIZE)


def test_outside_a_call_nothing_is_recorded():
    n = len(spans.calls())
    with spans.span("compress.prep") as s:
        spans.count("h2d_bytes", 5)
        y = spans.to_host(spans.to_device(np.arange(4)))
    assert s is None and y.tolist() == [0, 1, 2, 3]
    assert len(spans.calls()) == n


def test_an_exception_still_closes_the_record():
    with pytest.raises(ValueError), spans.call("compress") as rec:
        with spans.span("compress.encode"):
            raise ValueError("boom")
    assert rec.t1 >= rec.spans[0].t1 > 0
    assert spans.calls()[-1] is rec
    with spans.call("decompress") as rec2:  # the thread's state was cleared
        pass
    assert isinstance(rec2, spans.Record) and not rec2.spans


def test_the_log_is_bounded():
    for i in range(spans.LOG_SIZE + 5):
        with spans.call(f"c{i}"):
            pass
    log = spans.calls()
    assert len(log) == spans.LOG_SIZE
    assert [r.name for r in log[-2:]] == [f"c{spans.LOG_SIZE + 3}", f"c{spans.LOG_SIZE + 4}"]


def test_a_compile_is_counted_in_the_innermost_open_span():
    k = np.float32(np.random.default_rng().random())
    fn = jax.jit(lambda v: v * k + 1.0)  # a new program: it must compile
    arg = jnp.ones(8, jnp.float32)
    arg.block_until_ready()
    with spans.call("compress") as rec:
        with spans.span("outer") as outer:
            with spans.span("inner") as inner:
                fn(arg).block_until_ready()
    assert rec.counters["compiles"] >= 1
    assert inner.compile_s > 0 and outer.compile_s == 0.0
    assert inner.compile_s == pytest.approx(rec.counters["compile_s"])


def test_calls_on_one_compressor_keep_their_own_records():
    """The compressd worker pool shares one Compressor between threads:
    each thread's ``last_telemetry["trace"]`` is its own call's record."""
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="tp", autotune=False))
    sides = [16, 20, 24, 28]
    barrier = threading.Barrier(len(sides))
    got, failures = {}, []

    def run(n):
        try:
            x = field((n, n, n), n)
            for _ in range(2):
                barrier.wait(timeout=60)
                comp.compress(x)
                rec = comp.last_telemetry["trace"]
                assert rec.name == "compress" and rec.counters["in_bytes"] == x.nbytes
                assert sum(s.name == "compress.verify" for s in rec.spans) == 1
                assert sum(s.name == "decompress" for s in rec.spans) == 1  # verify's own decode
                got.setdefault(n, []).append(rec)
        except Exception as e:  # pragma: no cover - failure path
            failures.append((n, repr(e)))

    threads = [threading.Thread(target=run, args=(n,)) for n in sides]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not failures, failures
    recs = [r for n in sides for r in got[n]]
    assert len({id(r) for r in recs}) == 2 * len(sides)
    log = spans.calls()
    assert all(any(r is q for q in log) for r in recs)


AVAL = re.compile(r"host-to-device transfer: aval=ShapedArray\((\w+)\[([\d,]+)\]")


@contextlib.contextmanager
def crossings(monkeypatch):
    """Array bytes that cross while inside: host to device from JAX's
    transfer guard log (stderr), device to host from the arrays' buffer
    protocol and ``__array__``. Scalars are left out."""
    seen = {"h2d": 0, "d2h": 0}
    cls = type(jnp.zeros(1))
    buffer, array = cls.__buffer__, cls.__array__

    def counted(orig):
        def f(self, *a, **k):
            if self.ndim:
                seen["d2h"] += self.nbytes
            return orig(self, *a, **k)
        return f

    monkeypatch.setattr(cls, "__buffer__", counted(buffer))
    monkeypatch.setattr(cls, "__array__", counted(array))
    with tempfile.TemporaryFile() as log:
        saved = os.dup(2)
        os.dup2(log.fileno(), 2)
        try:
            with jax.transfer_guard_host_to_device("log_explicit"):
                yield seen
        finally:
            os.dup2(saved, 2)
            os.close(saved)
            monkeypatch.undo()
        log.seek(0)
        for dtype, dims in AVAL.findall(log.read().decode(errors="replace")):
            seen["h2d"] += int(np.prod([int(d) for d in dims.split(",")])) * np.dtype(dtype).itemsize


@pytest.mark.parametrize("engine", ["numpy", "device"])
def test_transfer_counters_equal_the_bytes_that_cross(engine, monkeypatch):
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="tp" if engine == "device" else "cr", engine=engine))
    x = field((40, 36, 33), 3)
    comp.decompress(comp.compress(x))  # compile outside the measured calls
    with crossings(monkeypatch) as seen:
        buf = comp.compress(x)
        c = comp.last_telemetry["trace"].counters
        y = comp.decompress(buf)
        d = comp.last_telemetry["trace"].counters
    assert c["in_bytes"] == x.nbytes and d["in_bytes"] == len(buf)
    assert c["h2d_bytes"] > 0 and c["d2h_bytes"] > 0
    assert seen["h2d"] == c["h2d_bytes"] + d["h2d_bytes"]
    assert seen["d2h"] == c["d2h_bytes"] + d["d2h_bytes"]
    assert y.shape == x.shape


def test_a_device_input_is_counted_as_it_crosses():
    x = field((24, 20, 18), 4)
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="tp", autotune=False))
    buf = comp.compress(jnp.asarray(x))
    rec = comp.last_telemetry["trace"]
    assert rec.counters["in_bytes"] == x.nbytes and rec.counters["d2h_bytes"] >= x.nbytes
    assert buf == comp.compress(x)


def test_decode_telemetry_reads_the_record():
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False))
    buf = comp.compress(field((24, 20, 18), 5))
    comp.decompress(buf)
    tel = comp.last_telemetry
    rec = tel["trace"]
    assert rec.name == "decompress" and tel["decode"]["seconds"] == rec.seconds > 0
    assert {s.name for s in rec.spans} >= {"decompress.unpack", "decompress.lossless", "decompress.restore",
                                           "decompress.reconstruct", "decompress.scatter"}


def test_compress_spans_cover_the_call():
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="cr"))
    comp.compress(field((40, 36, 33), 6))
    rec = comp.last_telemetry["trace"]
    top = {s.name for s in rec.spans if s.parent < 0}
    assert top == {"compress.ingest", "compress.prep", "compress.tune", "compress.predict", "compress.scatter",
                   "compress.reorder", "compress.encode", "compress.pack", "compress.verify"}
    assert {"encode.hf", "encode.rre4", "encode.tcms8", "encode.rze1"} <= {s.name for s in rec.spans}
    assert all(not s.name.startswith("bench:") for s in rec.spans)
    assert sum(s.seconds for s in rec.spans if s.parent < 0) >= 0.95 * rec.seconds


def test_a_stream_stored_through_compiles_nothing_that_follows_its_data():
    """Two random streams of one length whose kept-row counts fall in
    different row buckets: rre1 expands on both, so the second encode is
    decided from its size and compiles no program shaped by the count."""
    from repro.core.lossless import pipelines

    rng = np.random.default_rng(9)
    a = rng.integers(0, 256, 200_000, dtype=np.uint8)
    b = a.copy()
    b[1::10] = b[:-1:10]  # one byte in ten repeats: ~10% fewer kept rows
    recs = []
    for data in (a, b):
        with spans.call("compress") as rec:
            pipelines.encode(jnp.asarray(data), "tp")
        recs.append(rec)
    assert [r.counters.get("store_through_early", 0) for r in recs] == [1, 1]
    assert recs[1].counters["compiles"] == 0


# Containers written before the spans existed, for the same fields: the
# recording changes no byte.
GOLDEN = {
    ("cr", (24, 20, 18), 11): "d206f6bf0e3ba9a9e7840ebf841198eed4d05545a927248110ca73d505e22ec2",
    ("cr", (2, 17, 30, 9), 12): "dcf0a49290d448ee1420ae11d21666a0c82266aa6f93f4230741679286967562",
    ("tp", (24, 20, 18), 11): "11c3e7a5f4d45131346c5ce507aaeb2091d4116db62eb6c4fc7acec51b1e3bd7",
    ("tp", (2, 17, 30, 9), 12): "cd8e0d886be2c8c5f0f060c024199a22df1d0c793f8a523dc9d7c7f9ed977428",
    ("tp-pallas", (24, 20, 18), 11): "11c3e7a5f4d45131346c5ce507aaeb2091d4116db62eb6c4fc7acec51b1e3bd7",
}
SPECS = {"cr": {"pipeline": "cr"}, "tp": {"pipeline": "tp", "engine": "device"},
         "tp-pallas": {"pipeline": "tp", "engine": "device", "backend": "pallas"}}


@pytest.mark.parametrize("mode,shape,seed", list(GOLDEN))
def test_containers_are_byte_identical(mode, shape, seed):
    buf = Compressor(CompressorSpec(eb=1e-3, **SPECS[mode])).compress(field(shape, seed))
    assert hashlib.sha256(buf).hexdigest() == GOLDEN[(mode, shape, seed)]
