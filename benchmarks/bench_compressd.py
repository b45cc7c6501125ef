"""Service-lane benchmark: N concurrent clients against a compressd daemon.

    PYTHONPATH=src python -m benchmarks.bench_compressd --clients 8 --smoke \
        --out bench_compressd_smoke.json

Boots an in-process :class:`repro.launch.compressd.CompressdServer` (or
targets an external one via ``--addr``), then drives ``--clients``
threads, each cycling a small set of *recurring* field shapes through
compress + decompress roundtrips — the daemon's design load, where the
shared plan cache should absorb every tuning cost after warmup.

Reported per op kind: p50/p99 latency (ms), aggregate MB/s across all
clients, CR. The plan-cache claim is **asserted, not just timed**: after
a one-pass warmup, every measured compress response must report
``plan_cache == "hit"`` (each client echoes the daemon's per-response
telemetry); any miss fails the bench with a nonzero exit. Peak admitted
bytes stay bounded by the daemon's in-flight budget, and the run checks
the budget drains back to zero at the end.

The JSON output carries the grid (smoke flag, clients, shapes, eb) so
``benchmarks.check_service_regression`` can refuse to compare unlike
runs. Timing gates belong to the checker, with generous machine-variance
tolerance; CR and the hit assertion are deterministic.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np

from repro.launch.compressd import CompressdClient, CompressdServer

FULL_SHAPES = [(64, 64, 64), (32, 64, 64), (96, 96)]
SMOKE_SHAPES = [(24, 24, 24), (16, 24, 24), (48, 48)]


def _make_fields(shapes) -> list[np.ndarray]:
    """One seeded smooth-plus-noise field per shape, shared by all clients
    (identical bytes -> identical plan signatures -> recurring load)."""
    fields = []
    for seed, shape in enumerate(shapes):
        rng = np.random.default_rng(seed)
        axes = [np.linspace(0, 4 * np.pi, n) for n in shape]
        mesh = np.meshgrid(*axes, indexing="ij")
        x = np.ones(shape, np.float32)
        for i, m in enumerate(mesh):
            x = x * np.sin(m + 0.3 * i).astype(np.float32)
        x += 0.01 * rng.standard_normal(shape).astype(np.float32)
        fields.append(np.ascontiguousarray(x, np.float32))
    return fields


def _spec(eb: float) -> str:
    # canonical spec-string grammar (CompressorSpec.from_string)
    return f"lossy,rel,{eb:g},predictor=auto,pipeline=auto"


def _percentiles(ms: list[float]) -> dict:
    arr = np.asarray(ms, np.float64)
    return {"p50_ms": float(np.percentile(arr, 50)),
            "p99_ms": float(np.percentile(arr, 99)),
            "mean_ms": float(arr.mean()), "n": int(arr.size)}


def run(addr: str, fields, *, clients: int, requests: int, eb: float) -> dict:
    # ---- warmup: populate the plan cache (and jit caches) once per shape
    containers = {}
    with CompressdClient(addr, stream="bench-warmup") as c:
        for i, x in enumerate(fields):
            containers[i] = c.compress(x, spec=_spec(eb))
            c.decompress(containers[i])

    comp_lat: list[float] = []
    deco_lat: list[float] = []
    misses: list[dict] = []
    raw_bytes = [0]
    comp_bytes = [0]
    errors: list[str] = []
    lock = threading.Lock()
    start_gate = threading.Barrier(clients + 1)

    def client_loop(k: int):
        try:
            with CompressdClient(addr, stream=f"bench-{k}") as c:
                start_gate.wait(timeout=60)
                for j in range(requests):
                    x = fields[(k + j) % len(fields)]
                    t0 = time.perf_counter()
                    buf = c.compress(x, spec=_spec(eb))
                    dt_c = time.perf_counter() - t0
                    info = dict(c.last_info or {})
                    t0 = time.perf_counter()
                    y = c.decompress(buf)
                    dt_d = time.perf_counter() - t0
                    if y.shape != x.shape:
                        raise RuntimeError(f"shape mismatch {y.shape} != {x.shape}")
                    with lock:
                        comp_lat.append(dt_c * 1e3)
                        deco_lat.append(dt_d * 1e3)
                        raw_bytes[0] += x.nbytes
                        comp_bytes[0] += len(buf)
                        if info.get("plan_cache") != "hit":
                            misses.append({"client": k, "req": j, "shape": list(x.shape),
                                           "plan_cache": info.get("plan_cache")})
        except Exception as e:  # pragma: no cover - failure path
            with lock:
                errors.append(f"client {k}: {e!r}")

    threads = [threading.Thread(target=client_loop, args=(k,)) for k in range(clients)]
    for t in threads:
        t.start()
    start_gate.wait(timeout=60)
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError("client failures: " + "; ".join(errors))

    with CompressdClient(addr) as c:
        stats = c.stats()
    n_ops = len(comp_lat)
    doc = {
        "compress": {**_percentiles(comp_lat),
                     "mbps_aggregate": raw_bytes[0] / (sum(comp_lat) / 1e3) / 1e6 * clients
                     if comp_lat else 0.0},
        "decompress": {**_percentiles(deco_lat),
                       "mbps_aggregate": raw_bytes[0] / (sum(deco_lat) / 1e3) / 1e6 * clients
                       if deco_lat else 0.0},
        "wall_seconds": wall,
        "roundtrips_per_s": n_ops / wall if wall > 0 else 0.0,
        # bytes crossing the compressor in both directions over wall clock:
        # the number a capacity plan would use
        "mbps_wall": (2 * raw_bytes[0]) / wall / 1e6 if wall > 0 else 0.0,
        "cr": raw_bytes[0] / max(comp_bytes[0], 1),
        "plan_cache": stats["plan_cache"],
        "plan_cache_ok": not misses,
        "plan_cache_misses_post_warmup": misses,
        "inflight_bytes_at_end": stats["queue"]["inflight_bytes"],
    }
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=None,
                    help="roundtrips per client (default: 4 smoke, 12 full)")
    ap.add_argument("--eb", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true", help="small fields for CI")
    ap.add_argument("--addr", default=None,
                    help="target an already-running daemon instead of in-process")
    ap.add_argument("--workers", type=int, default=4, help="in-process daemon width")
    ap.add_argument("--out", default=None, help="write the result JSON here")
    args = ap.parse_args(argv)
    from repro.launch.jaxcache import enable_compile_cache

    enable_compile_cache()

    shapes = SMOKE_SHAPES if args.smoke else FULL_SHAPES
    requests = args.requests if args.requests is not None else (4 if args.smoke else 12)
    fields = _make_fields(shapes)

    server = None
    addr = args.addr
    if addr is None:
        server = CompressdServer("127.0.0.1:0", workers=args.workers).start()
        addr = server.address
    try:
        doc = run(addr, fields, clients=args.clients, requests=requests, eb=args.eb)
    finally:
        if server is not None:
            server.close()

    doc = {
        "bench": "compressd",
        "smoke": bool(args.smoke),
        "clients": args.clients,
        "requests_per_client": requests,
        "eb": args.eb,
        "shapes": [list(s) for s in shapes],
        **doc,
    }
    print(json.dumps(doc, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    if not doc["plan_cache_ok"]:
        print(f"FAIL: {len(doc['plan_cache_misses_post_warmup'])} post-warmup compress "
              "responses were not plan-cache hits", file=sys.stderr)
        return 1
    if doc["inflight_bytes_at_end"] != 0:
        print("FAIL: in-flight byte budget did not drain to zero", file=sys.stderr)
        return 1
    c, d = doc["compress"], doc["decompress"]
    print(f"compressd bench: {args.clients} clients x {requests} roundtrips, "
          f"compress p50 {c['p50_ms']:.1f} ms / p99 {c['p99_ms']:.1f} ms, "
          f"decompress p50 {d['p50_ms']:.1f} ms / p99 {d['p99_ms']:.1f} ms, "
          f"CR {doc['cr']:.2f}, plan-cache hits asserted on all "
          f"{c['n']} measured ops", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
