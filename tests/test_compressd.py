"""compressd daemon: protocol, concurrency, backpressure, degradation.

Daemon tests carry explicit ``pytest.mark.timeout`` marks (active when
pytest-timeout is installed, as in CI; inert otherwise) so a wedged
socket or a deadlocked admission queue fails the run instead of hanging
it.
"""
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import Compressor, CompressorSpec, PlanCache
from repro.core.errors import (
    RequestTooLargeError,
    ServiceError,
    ServiceOverloadedError,
    ServiceProtocolError,
)
from repro.launch.compressd import (
    MAGIC,
    CompressdClient,
    CompressdServer,
    default_workers,
    pack_frame,
    parse_addr,
    read_frame,
    wait_ready,
)

pytestmark = pytest.mark.timeout(120)


def _field(seed=0, n=24):
    g = np.linspace(0, 4 * np.pi, n)
    X, Y, Z = np.meshgrid(g, g, g, indexing="ij")
    rng = np.random.default_rng(seed)
    return (np.sin(X + seed) * np.cos(Y) * np.sin(Z)
            + 0.01 * rng.standard_normal(X.shape)).astype(np.float32)


@pytest.fixture(scope="module")
def server():
    with CompressdServer("127.0.0.1:0", workers=4).start() as srv:
        wait_ready(srv.address, timeout=10)
        yield srv


# ----------------------------------------------------------------- protocol
def test_parse_addr():
    assert parse_addr("127.0.0.1:7733") == (socket.AF_INET, ("127.0.0.1", 7733))
    assert parse_addr("unix:/tmp/x.sock") == (socket.AF_UNIX, "/tmp/x.sock")
    with pytest.raises(ValueError):
        parse_addr("7733")


def test_ping_and_stats_shape(server):
    with CompressdClient(server.address) as c:
        assert c.ping()
        st = c.stats()
    assert st["workers"] == 4
    assert {"inflight_bytes", "queued", "queue_depth", "rejected_overload",
            "rejected_oversize"} <= set(st["queue"])
    assert {"entries", "hits", "misses", "hit_rate"} <= set(st["plan_cache"])


def test_bad_magic_gets_protocol_error(server):
    family, sockaddr = parse_addr(server.address)
    with socket.socket(family, socket.SOCK_STREAM) as s:
        s.settimeout(10)
        s.connect(sockaddr)
        s.sendall(b"NOPE" + b"\x00" * 12)
        rh, _ = read_frame(s)
    assert rh["ok"] is False and rh["error"] == "ServiceProtocolError"


def test_unknown_op_and_bad_shape(server):
    with CompressdClient(server.address) as c:
        with pytest.raises(ServiceProtocolError):
            c.request({"op": "frobnicate"}, b"x")
        # connection survives a typed rejection
        with pytest.raises(ServiceProtocolError):
            c.request({"op": "compress", "shape": [10, 10], "dtype": "float32"},
                      b"\x00" * 12)  # 12 B != 400 B
        assert c.ping()


def test_unknown_spec_field_rejected(server):
    with CompressdClient(server.address) as c:
        with pytest.raises(ServiceProtocolError, match="unknown spec field"):
            c.compress(_field(), ebb=1e-3)  # typo must not silently default
        with pytest.raises(ValueError):
            c.compress(_field(), eb=1e-3, pipeline="not-a-pipeline")
        assert c.ping()


# ------------------------------------------------------------ compress path
def test_roundtrip_and_plan_cache_hit(server):
    x = _field(3)
    with CompressdClient(server.address, stream="t-roundtrip") as c:
        buf = c.compress(x, eb=1e-3, predictor="auto", pipeline="auto")
        first = dict(c.last_info)
        c.compress(x, eb=1e-3, predictor="auto", pipeline="auto")
        second = dict(c.last_info)
        y = c.decompress(buf)
        st = c.stats()
    assert first["plan_cache"] == "miss" and second["plan_cache"] == "hit"
    assert second["pipeline"] == first["pipeline"]
    assert first["repairs"] == second["repairs"] == 0  # verify found the bound held
    assert y.shape == x.shape and y.dtype == np.float32
    assert np.max(np.abs(x - y)) <= 1e-3 * (x.max() - x.min()) * (1 + 1e-5)
    rec = st["streams"]["t-roundtrip"]
    assert rec["requests"] == 3 and rec["plan_cache_hits"] >= 1
    assert rec["cr"] > 0 and rec["mbps"] > 0


def test_stream_stats_carry_span_self_seconds(server):
    x = _field(5)
    with CompressdClient(server.address, stream="t-spans") as c:
        c.decompress(c.compress(x, eb=1e-3, pipeline="tp", autotune=False))
        rec = c.stats()["streams"]["t-spans"]
    sp = rec["spans"]
    assert {"compress", "compress.prep", "compress.encode", "compress.verify",
            "decompress", "decompress.lossless", "decompress.restore"} <= set(sp)
    assert all(v >= -1e-9 for v in sp.values())
    assert sum(sp.values()) <= rec["seconds"]


def test_spec_variants_roundtrip(server):
    x = _field(4)
    with CompressdClient(server.address) as c:
        for spec in ({"eb": 1e-2}, {"eb": 1e-3, "eb_mode": "abs"},
                     {"eb": 1e-3, "pipeline": "tp", "autotune": False}):
            buf = c.compress(x, **spec)
            y = c.decompress(buf)
            assert y.shape == x.shape


def test_daemon_matches_local_compressor(server):
    """A daemon container is a normal container: local decode, same bound."""
    x = _field(5)
    with CompressdClient(server.address) as c:
        buf = c.compress(x, eb=1e-3, pipeline="tp", autotune=False)
    local = Compressor(CompressorSpec(eb=1e-3, pipeline="tp", autotune=False))
    assert np.array_equal(local.decompress(buf), local.decompress(local.compress(x)))


# ---------------------------------------------------------------- concurrency
def test_concurrent_clients(server):
    """N clients hammer concurrently; every roundtrip lands within bound."""
    n_clients, per_client = 6, 3
    fields = [_field(seed, n=20) for seed in range(n_clients)]
    errors = []

    def run(k):
        try:
            with CompressdClient(server.address, stream=f"conc-{k}") as c:
                for _ in range(per_client):
                    buf = c.compress(fields[k], eb=1e-3)
                    y = c.decompress(buf)
                    assert np.max(np.abs(fields[k] - y)) <= \
                        1e-3 * (fields[k].max() - fields[k].min()) * (1 + 1e-5)
        except Exception as e:  # pragma: no cover - failure path
            errors.append((k, repr(e)))

    threads = [threading.Thread(target=run, args=(k,)) for k in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=110)
    assert not errors, errors
    st = CompressdClient(server.address).stats()
    for k in range(n_clients):
        assert st["streams"][f"conc-{k}"]["requests"] == 2 * per_client
        assert st["streams"][f"conc-{k}"]["errors"] == 0


# ------------------------------------------------------------- backpressure
@pytest.mark.timeout(60)
def test_backpressure_queue_then_shed():
    """In-flight byte budget: 1st holds it, 2nd queues, 3rd is shed."""
    with CompressdServer("127.0.0.1:0", workers=4, max_request_bytes=1 << 20,
                         max_inflight_bytes=1 << 20, queue_depth=1).start() as srv:
        hold = b"\x00" * (1 << 20)
        results = {}

        def sleeper(name, seconds):
            try:
                with CompressdClient(srv.address) as c:
                    rh, _ = c.request({"op": "sleep", "seconds": seconds}, hold)
                    results[name] = rh
            except ServiceError as e:
                results[name] = e

        t1 = threading.Thread(target=sleeper, args=("a", 1.2))
        t1.start()
        time.sleep(0.4)  # a is admitted and holds the whole budget
        t2 = threading.Thread(target=sleeper, args=("b", 0.1))
        t2.start()
        time.sleep(0.4)  # b is parked in the admission queue (depth 1)
        t3 = threading.Thread(target=sleeper, args=("c", 0.1))
        t3.start()
        t3.join(timeout=30)
        assert isinstance(results["c"], ServiceOverloadedError)  # shed, typed
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert results["a"]["ok"] and results["b"]["ok"]  # queued b completed
        for _ in range(100):  # released just after each reply is sent
            st = srv.stats()
            if st["queue"]["inflight_bytes"] == 0:
                break
            time.sleep(0.05)
        assert st["queue"]["rejected_overload"] == 1
        assert st["queue"]["inflight_bytes"] == 0  # budget fully released


@pytest.mark.timeout(60)
def test_oversized_request_rejected_and_connection_survives():
    with CompressdServer("127.0.0.1:0", workers=2,
                         max_request_bytes=1 << 16).start() as srv:
        with CompressdClient(srv.address) as c:
            with pytest.raises(RequestTooLargeError):
                c.compress(np.zeros((256, 256), np.float32))  # 256 KiB > 64 KiB
            # payload was drained, not buffered: framing intact, daemon alive
            assert c.ping()
            small = np.zeros((64, 64), np.float32)
            assert isinstance(c.compress(small, eb=1e-3), bytes)
            assert srv.stats()["queue"]["rejected_oversize"] == 1


def test_compress_error_is_typed_and_worker_survives(server):
    with CompressdClient(server.address) as c:
        bad = np.full((20, 20, 20), np.nan, np.float32)
        try:
            c.compress(bad, eb=1e-3)  # NaN field may or may not raise...
        except Exception:
            pass
        with pytest.raises((ServiceError, ValueError)):
            c.decompress(b"this is not a container")
        assert c.ping()  # ...but the daemon always survives


# --------------------------------------------------------- shared plan cache
def test_shared_cache_across_connections():
    cache = PlanCache(max_entries=8)
    with CompressdServer("127.0.0.1:0", workers=2, plan_cache=cache).start() as srv:
        x = _field(7)
        with CompressdClient(srv.address) as c1:
            c1.compress(x, eb=1e-3, predictor="auto", pipeline="auto")
            assert c1.last_info["plan_cache"] == "miss"
        with CompressdClient(srv.address) as c2:  # new connection, same cache
            c2.compress(x, eb=1e-3, predictor="auto", pipeline="auto")
            assert c2.last_info["plan_cache"] == "hit"
        assert cache.stats()["hits"] == 1


# -------------------------------------------------- telemetry thread-safety
@pytest.mark.timeout(60)
def test_compressor_telemetry_is_per_thread():
    """Regression: one Compressor shared across threads must not cross-wire
    ``last_telemetry`` between concurrent calls (the daemon's worker pool
    shares per-spec instances)."""
    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="tp", autotune=False))
    sizes = [16, 20, 24, 28]
    bufs = {n: comp.compress(_field(1, n=n)) for n in sizes}
    barrier = threading.Barrier(len(sizes))
    failures = []

    def run(n):
        try:
            for _ in range(5):
                barrier.wait(timeout=30)
                out = comp.decompress(bufs[n])
                tel = comp.last_telemetry
                # this thread's view must describe THIS call
                assert tel["decode"]["bytes"] == out.nbytes == n ** 3 * 4
        except Exception as e:  # pragma: no cover - failure path
            failures.append((n, repr(e)))

    threads = [threading.Thread(target=run, args=(n,)) for n in sizes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=50)
    assert not failures, failures


# ------------------------------------------------------------------ CLI/env
def test_env_knob_workers(monkeypatch):
    monkeypatch.setenv("REPRO_COMPRESSD_WORKERS", "7")
    assert default_workers() == 7
    monkeypatch.setenv("REPRO_COMPRESSD_WORKERS", "bogus")
    assert default_workers() == 4
    monkeypatch.delenv("REPRO_COMPRESSD_WORKERS")
    srv = CompressdServer("127.0.0.1:0", workers=3, queue_depth=5)
    try:
        assert srv.workers == 3 and srv.queue_depth == 5
    finally:
        srv.close()


def test_unix_socket_roundtrip(tmp_path):
    addr = f"unix:{tmp_path}/compressd.sock"
    with CompressdServer(addr, workers=2).start() as srv:
        assert srv.address == addr
        with CompressdClient(addr) as c:
            x = _field(8, n=16)
            y = c.decompress(c.compress(x, eb=1e-2))
            assert y.shape == x.shape
    assert not (tmp_path / "compressd.sock").exists()  # unlinked on close


@pytest.mark.timeout(120)
def test_cli_subprocess_serves_and_shuts_down():
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.compressd", "--addr", "127.0.0.1:0",
         "--workers", "2"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        line = proc.stdout.readline()
        assert "compressd listening on " in line, line
        addr = line.split("compressd listening on ")[1].split()[0]
        wait_ready(addr, timeout=60)
        with CompressdClient(addr) as c:
            x = _field(9, n=16)
            y = c.decompress(c.compress(x, eb=1e-2))
            assert np.max(np.abs(x - y)) <= 1e-2 * (x.max() - x.min()) * (1 + 1e-5)
            c.shutdown()
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_frame_codec_symmetry():
    hdr = {"op": "ping", "n": 3}
    frame = pack_frame(hdr, b"payload")
    assert frame.startswith(MAGIC)
    # decode through a socketpair to exercise the exact recv path
    a, b = socket.socketpair()
    try:
        a.sendall(frame)
        rh, rp = read_frame(b)
    finally:
        a.close()
        b.close()
    assert rh == hdr and rp == b"payload"
    (hlen,) = struct.unpack_from("<I", frame, 4)
    assert len(frame) == 4 + 4 + hlen + 8 + len(b"payload")


# ------------------------------------------------------- spec-string surface
def test_spec_string_roundtrip_no_warning(server):
    import warnings as W

    from repro.core import SpecError

    x = _field(9)
    with CompressdClient(server.address, stream="t-specstr") as c:
        with W.catch_warnings():
            W.simplefilter("error", DeprecationWarning)
            buf = c.compress(x, spec="lossy,abs,1e-3,autotune=false")
            y = c.decompress(buf)
        assert np.max(np.abs(x - y)) <= 1e-3 * (1 + 1e-4) + 1e-9
        # CompressorSpec objects are accepted and canonicalized client-side
        buf2 = c.compress(x, spec=CompressorSpec(eb=1e-3, eb_mode="abs", autotune=False))
        assert len(buf2) == len(buf)
        # bad grammar fails client-side with the typed error, nothing sent
        with pytest.raises(SpecError):
            c.compress(x, spec="lossy,abs,oops")


def test_legacy_spec_kwargs_deprecated_but_equivalent(server):
    x = _field(9)
    with CompressdClient(server.address, stream="t-legacy") as c:
        with pytest.warns(DeprecationWarning, match="deprecated"):
            legacy = c.compress(x, eb=1e-3, eb_mode="abs", autotune=False)
        modern = c.compress(x, spec="lossy,abs,1e-3,autotune=false")
        y = c.decompress(legacy)
        assert np.max(np.abs(x - y)) <= 1e-3 * (1 + 1e-4) + 1e-9
        assert len(legacy) == len(modern)  # same spec through either surface
        with pytest.raises(TypeError, match="not both"):
            c.compress(x, spec="lossy,abs,1e-3", eb=1e-3)


# ------------------------------------------------------------ survivability
def test_deadline_exceeded_typed_and_bytes_released():
    from repro.core.errors import DeadlineExceededError

    with CompressdServer("127.0.0.1:0", workers=1, deadline_ms=150).start() as srv:
        with CompressdClient(srv.address) as c:
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceededError):
                c.request({"op": "sleep", "seconds": 3.0}, b"x" * 256)
            assert time.monotonic() - t0 < 2.0  # responded at the deadline, not after
            assert c.ping()  # connection framing survived
            # the stranded worker's reservation drains once the sleep ends
            for _ in range(100):
                q = c.stats()["queue"]
                if q["inflight_bytes"] == 0:
                    break
                time.sleep(0.1)
            assert q["inflight_bytes"] == 0
            assert q["deadline_exceeded"] >= 1


def test_deadline_off_by_default(server):
    assert server.deadline_ms == 0.0
    with CompressdClient(server.address) as c:
        rh, _ = c.request({"op": "sleep", "seconds": 0.2}, b"y" * 16)
        assert rh["ok"]


def test_health_op_bypasses_admission(server):
    with CompressdClient(server.address) as c:
        h = c.health()
        assert h["healthy"] and not h["draining"]
        assert "inflight_bytes" in h and "queued" in h


def test_drain_finishes_inflight_sheds_new():
    srv = CompressdServer("127.0.0.1:0", workers=2, drain_s=15).start()
    slow = CompressdClient(srv.address)
    probe = CompressdClient(srv.address)
    probe.ping()  # connection established before the drain begins
    done = {}

    def run_slow():
        done["resp"] = slow.request({"op": "sleep", "seconds": 1.0}, b"z" * 64)

    def wait_for(cond):  # poll health, not the clock: a loaded host is slow
        deadline = time.monotonic() + 10
        while not cond(probe.health()):
            assert time.monotonic() < deadline, probe.health()
            time.sleep(0.01)

    t = threading.Thread(target=run_slow)
    t.start()
    wait_for(lambda h: h["inflight_bytes"] > 0)  # the slow request is in flight
    drainer = threading.Thread(target=srv.drain)
    drainer.start()
    wait_for(lambda h: h["draining"])
    # new work on a live connection is shed while draining...
    with pytest.raises(ServiceOverloadedError):
        probe.request({"op": "sleep", "seconds": 0.1}, b"w" * 16)
    # ...but health still answers, reporting the drain
    assert probe.health()["draining"]
    t.join(timeout=10)
    drainer.join(timeout=10)
    # the in-flight request completed during the drain window
    assert done["resp"][0]["ok"]
    # and the daemon is fully closed: new connections are refused
    with pytest.raises((ConnectionError, OSError)):
        CompressdClient(srv.address).ping()
    slow.close()
    probe.close()


def test_drain_unlinks_unix_socket(tmp_path):
    import os

    path = str(tmp_path / "drain.sock")
    srv = CompressdServer(f"unix:{path}").start()
    wait_ready(srv.address, timeout=10)
    srv.drain()
    assert not os.path.exists(path)


@pytest.mark.timeout(120)
def test_sigterm_drains_under_load():
    """SIGTERM to the CLI daemon with a request in flight: the in-flight
    request completes, new work is shed, and the process exits cleanly."""
    import signal as _signal

    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.compressd", "--addr", "127.0.0.1:0",
         "--workers", "2", "--drain-s", "20"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        line = proc.stdout.readline()
        assert "compressd listening on " in line, line
        addr = line.split("compressd listening on ")[1].split()[0]
        wait_ready(addr, timeout=60)
        inflight = {}

        def slow_request():
            with CompressdClient(addr) as c:
                rh, _ = c.request({"op": "sleep", "seconds": 1.5}, b"x" * 64)
                inflight["rh"] = rh

        t = threading.Thread(target=slow_request)
        t.start()
        with CompressdClient(addr) as probe:  # poll health, not the clock: a loaded host is slow
            deadline = time.monotonic() + 10
            while probe.health()["inflight_bytes"] == 0:  # until the sleep is in flight on a worker
                assert time.monotonic() < deadline, probe.health()
                time.sleep(0.01)
        proc.send_signal(_signal.SIGTERM)
        t.join(timeout=30)
        assert inflight["rh"]["ok"]  # in-flight work finished during the drain
        assert proc.wait(timeout=30) == 0
        with pytest.raises((ConnectionError, OSError)):
            CompressdClient(addr).ping()  # daemon is gone, not wedged
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_stale_unix_socket_reclaimed(tmp_path):
    import os

    path = str(tmp_path / "stale.sock")
    # a dead daemon's leftover: bound once, never unlinked
    leftover = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    leftover.bind(path)
    leftover.close()
    assert os.path.exists(path)
    with CompressdServer(f"unix:{path}").start() as srv:
        wait_ready(srv.address, timeout=10)
        with CompressdClient(srv.address) as c:
            assert c.ping()


def test_live_unix_socket_not_hijacked(tmp_path):
    path = str(tmp_path / "live.sock")
    with CompressdServer(f"unix:{path}").start() as srv:
        wait_ready(srv.address, timeout=10)
        with pytest.raises(OSError, match="live daemon"):
            CompressdServer(f"unix:{path}")
        with CompressdClient(srv.address) as c:
            assert c.ping()  # the incumbent is untouched


def test_idle_connection_reaped():
    with CompressdServer("127.0.0.1:0", idle_s=0.3).start() as srv:
        c = CompressdClient(srv.address)
        assert c.ping()
        time.sleep(0.9)
        with pytest.raises((ConnectionError, OSError)):
            c.ping()
        c.close()
        with CompressdClient(srv.address) as c2:  # daemon itself is fine
            assert c2.stats()["queue"]["idle_reaped"] >= 1


def test_client_retry_rides_out_restart_window():
    """A client with retries enabled survives transient connection loss:
    first attempt hits a dead port, the daemon 'comes back' before the
    retry (simulated by binding the listener between attempts)."""
    srv = CompressdServer("127.0.0.1:0").start()
    addr = srv.address
    srv.close()  # daemon gone: first attempt gets ECONNREFUSED
    revived = {}

    def revive():
        time.sleep(0.3)
        host, port = addr.rsplit(":", 1)
        revived["srv"] = CompressdServer(f"{host}:{port}").start()

    threading.Thread(target=revive).start()
    try:
        c = CompressdClient(addr, retries=8, retry_backoff_s=0.2)
        assert c.ping()  # retried through the dead window
        c.close()
    finally:
        for _ in range(50):
            if "srv" in revived:
                break
            time.sleep(0.1)
        revived["srv"].close()


def test_client_retry_default_off():
    srv = CompressdServer("127.0.0.1:0", workers=1, max_request_bytes=1 << 20,
                          max_inflight_bytes=1 << 20, queue_depth=0).start()
    with srv:
        blocker = CompressdClient(srv.address)
        t = threading.Thread(target=lambda: blocker.request(
            {"op": "sleep", "seconds": 1.5}, b"b" * (1 << 20)))
        t.start()
        time.sleep(0.3)
        with CompressdClient(srv.address) as c:  # retries=0: shed is surfaced raw
            with pytest.raises(ServiceOverloadedError):
                c.request({"op": "sleep", "seconds": 0.1}, b"c" * (1 << 19))
        t.join()
        blocker.close()


def test_verify_spec_key_accepted(server):
    x = _field(3)
    with CompressdClient(server.address) as c:
        buf = c.compress(x, spec="lossy,rel,1e-3,verify=full")
        y = c.decompress(buf)
        rng = float(x.max() - x.min())
        assert float(np.max(np.abs(x - y))) <= 1e-3 * rng * (1 + 2e-4)
