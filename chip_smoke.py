#!/usr/bin/env python3
"""Smoke test of the compressor's main path on a TPU, at the paper's size.

    python chip_smoke.py             # one chip: phases a-e on a 512^3 f32 field
    python chip_smoke.py --chips 4   # only the sharded path, over four chips

The data is a seeded Nyx-class lognormal density field at the published
512^3 f32 (512 MiB), built the way ``repro.data.fields`` builds its 256^3
crop. Every phase prints its compression ratio, its worst error over the
declared bound, and its cold (compile included) and steady seconds, then
checks the bound and that ``last_telemetry`` records the requested engine
with no verify repair. Any failure raises, so the exit code is non-zero.
The last line of standard output is one JSON object naming the device.

Without a TPU (``jax.default_backend() != "tpu"``) it exits non-zero at
once and prints no result. It runs in one process, which owns the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SIDE = 512
SEED = 2507
SLACK = 1 + 1e-4  # f32 rounding headroom of the systemwide bound contract


def log(msg: str) -> None:
    print(msg, flush=True)


def nyx_field(side: int, seed: int, snapshots: int = 0):
    """Nyx-class lognormal density ``exp(2u)`` of a spectral field ``u``.
    ``snapshots=k`` stacks k of them along axis 0, each ``u`` rolled by a
    different offset (one FFT instead of k: the host generates while the
    chips wait)."""
    import numpy as np

    from repro.data.fields import _spectral_field

    u = _spectral_field((side,) * 3, 2.0, seed)
    if snapshots:
        u = np.concatenate([np.roll(u, (i * side // 4, i * side // 8, i * side // 16), (0, 1, 2))
                            for i in range(snapshots)])
    return np.exp(2.0 * u).astype(np.float32)


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def err_over_bound(x, y, buf) -> float:
    import numpy as np

    from repro.core import Compressor

    eb = float(Compressor.inspect(buf)["eb_abs"])
    return float(np.max(np.abs(np.asarray(y, np.float64) - x))) / eb


def check_clean(comp, engine: str, decode: str | None = None) -> None:
    """No degraded path: the requested engine ran and verify repaired nothing."""
    tel = comp.last_telemetry
    assert tel["engine"] == engine, tel
    assert tel.get("verify", {}).get("repairs", 0) == 0, tel
    if decode is not None:
        assert tel["decode"]["engine"] == decode, tel


def report(phase: str, x, buf, err: float, cold: float, steady: float | None, note: str = "") -> None:
    cr = x.nbytes / len(buf)
    st = "n/a" if steady is None else f"{steady:.2f}"
    log(f"[{phase}] CR {cr:.4f}  max err/bound {err:.7f}  cold {cold:.2f} s  steady {st} s  {note}")
    assert err <= SLACK, f"{phase}: max err/bound {err} > {SLACK}"


def phase_a(x):
    """Default entry: cusz_hi_cr, host input, host lossless engine."""
    from repro.core import cusz_hi_cr

    comp = cusz_hi_cr(eb=1e-3)
    buf, c0 = timed(lambda: comp.compress(x))
    _, c1 = timed(lambda: comp.compress(x))
    check_clean(comp, "auto")
    y, d0 = timed(lambda: comp.decompress(buf))
    _, d1 = timed(lambda: comp.decompress(buf))
    check_clean(comp, "auto", "numpy")
    err = err_over_bound(x, y, buf)
    report("a compress", x, buf, err, c0, c1)
    report("a decompress", x, buf, err, d0, d1)
    return buf


def phase_b(x, buf_a):
    """Device engine: same container bytes as (a), decode onto the device."""
    from repro.core import Compressor, CompressorSpec

    comp = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", engine="device"))
    buf, c0 = timed(lambda: comp.compress(x))
    _, c1 = timed(lambda: comp.compress(x))
    check_clean(comp, "device")
    assert buf == buf_a, "device engine container differs from the host engine's"
    y, d0 = timed(lambda: comp.decompress(buf, out="device"))
    _, d1 = timed(lambda: comp.decompress(buf, out="device"))
    check_clean(comp, "device", "device")
    assert not isinstance(y, type(x)), "decompress(out='device') returned a host array"
    err = err_over_bound(x, y, buf)
    report("b compress", x, buf, err, c0, c1, "container == a")
    report("b decompress", x, buf, err, d0, d1, "out=device")


def phase_c(x):
    """Pallas path: interp3d and bitshuffle compiled, pipeline tp."""
    from repro.core import Compressor, CompressorSpec

    comp = Compressor(CompressorSpec(eb=1e-3, backend="pallas", pipeline="tp", engine="device"))
    buf, c0 = timed(lambda: comp.compress(x))
    _, c1 = timed(lambda: comp.compress(x))
    check_clean(comp, "device")
    assert comp.last_telemetry["backend"] == "pallas"
    y, d0 = timed(lambda: comp.decompress(buf, out="device"))
    check_clean(comp, "device", "device")
    err = err_over_bound(x, y, buf)
    jax_dev = Compressor(CompressorSpec(eb=1e-3, pipeline="tp", engine="device"))
    buf_jax, j0 = timed(lambda: jax_dev.compress(x))
    check_clean(jax_dev, "device")
    # the Pallas bitshuffle against the host BIT1 encoder: engine bit-identity
    host = Compressor(CompressorSpec(eb=1e-3, pipeline="tp", engine="numpy"))
    assert host.compress(x) == buf_jax, "device tp container differs from the host engine's"
    same = buf == buf_jax
    report("c compress pallas", x, buf, err, c0, c1,
           f"pallas container {'==' if same else '!='} jax container ({len(buf)} vs {len(buf_jax)} B); "
           f"jax tp cold {j0:.2f} s")
    report("c decompress", x, buf, err, d0, None, "out=device")


def phase_d(x):
    """Cross-platform decode: chip-written rel 1e-4 container on the CPU
    (same floats as the chip's decode), CPU-written golden containers on
    the chip. The golden containers predate the ``arith`` header field, so
    they replay the matmul form on the host's XLA:CPU; the committed
    fixture is that replay under the installed JAX."""
    import jax
    import numpy as np

    from repro.core import Compressor, CompressorSpec
    from repro.core.predictor import ARITH

    spec = CompressorSpec(eb=1e-4, pipeline="cr")
    comp = Compressor(spec)
    buf, c0 = timed(lambda: comp.compress(x))
    check_clean(comp, "auto")
    assert Compressor.inspect(buf)["arith"] == ARITH
    y_chip = comp.decompress(buf)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        host = Compressor(spec)
        y_cpu, d0 = timed(lambda: host.decompress(buf))
    check_clean(host, "auto", "numpy")
    n_diff = int(np.count_nonzero(y_cpu != y_chip))
    err_chip, err_cpu = err_over_bound(x, y_chip, buf), err_over_bound(x, y_cpu, buf)
    report("d chip compress", x, buf, err_chip, c0, None, "rel 1e-4, chip decode")
    report("d cpu decode", x, buf, err_cpu, d0, None, f"{n_diff} points differ from the chip decode")
    assert n_diff == 0, f"CPU decode of the chip-written container differs in {n_diff} points"
    gold = ROOT / "tests" / "data"
    ref = np.load(gold / "golden_field.npy")
    for v in (1, 2, 3):
        blob = (gold / f"golden_v{v}.bin").read_bytes()
        g = Compressor(CompressorSpec(eb=1e-2, pipeline="cr", autotune=False))
        out, t = timed(lambda: g.decompress(blob))
        info = Compressor.inspect(blob)
        frames = info["frames"] if v == 3 else [info]
        bounds = np.concatenate([np.full(f["shape"][0], f["eb_abs"]) for f in frames])
        err = float(np.max(np.abs(out.astype(np.float64) - ref) / bounds[:, None, None]))
        fixture = np.load(gold / ("golden_decoded_v3.npy" if v == 3 else "golden_decoded.npy"))
        log(f"[d golden v{v}] max err/bound {err:.7f}  {t:.2f} s  "
            f"{int(np.count_nonzero(out != fixture))} points differ from the committed decode")
        assert err <= SLACK, f"golden v{v}: max err/bound {err}"


def phase_e(x):
    """Service: in-process compressd, ~8 requests of 16-64 MiB, one spec."""
    import numpy as np

    from repro.launch.compressd import CompressdClient, CompressdServer

    spec = "lossy,rel,1e-3,pipeline=cr"
    fields = {n: np.ascontiguousarray(x[:256, :256, :n]) for n in (64, 128, 256)}  # 16/32/64 MiB
    order = (64, 128, 256, 64, 128, 256, 64, 256)
    seen, worst, times = set(), 0.0, []
    with CompressdServer("127.0.0.1:0") as server:
        server.start()
        with CompressdClient(server.address) as client:
            for n in order:
                f = fields[n]
                buf, t = timed(lambda: client.compress(f, spec=spec))
                info = client.last_info
                assert info["repairs"] == 0, info
                if n in seen:
                    assert info["plan_cache"] == "hit", (n, info)
                seen.add(n)
                y = client.decompress(buf, spec=spec)
                err = err_over_bound(f, y, buf)
                assert err <= SLACK, f"compressd {f.shape}: max err/bound {err}"
                worst = max(worst, err)
                times.append(t)
                log(f"[e request] {f.nbytes >> 20} MiB  CR {f.nbytes / len(buf):.4f}  "
                    f"err/bound {err:.7f}  plan_cache {info['plan_cache']}  {t:.2f} s")
    log(f"[e compressd] {len(order)} requests  max err/bound {worst:.7f}  "
        f"first {times[0]:.2f} s  steady median {float(np.median(times[3:])):.2f} s")


def phase_shard(seed: int):
    """Four chips: shard_compress of a (2048, 512, 512) field against
    chunk_compress of the same field in four chunks on one chip."""
    import jax
    import numpy as np

    from repro.core import Compressor, CompressorSpec
    from repro.core.distributed import chunk_compress, default_mesh, shard_compress, shard_decompress

    ndev = len(jax.devices())
    assert ndev == 4, f"--chips 4 needs four devices, found {ndev}"
    x, t = timed(lambda: nyx_field(SIDE, seed, snapshots=4))
    log(f"field: four Nyx-class {SIDE}^3 snapshots, {x.shape} f32, seed {seed}, {t:.2f} s to generate")
    spec = CompressorSpec(eb=1e-3, pipeline="cr")
    comp = Compressor(spec)
    buf, s0 = timed(lambda: shard_compress(x, default_mesh(), compressor=comp))
    tel = comp.last_telemetry
    assert tel["shard"] == {"path": "shard_map", "ndev": 4}, tel
    ref, r0 = timed(lambda: chunk_compress(x, n_chunks=4, spec=spec))
    assert buf == ref, "sharded frames differ from chunk_compress"
    dcomp = Compressor(spec)
    y, d0 = timed(lambda: shard_decompress(buf, compressor=dcomp, out="device"))
    y = np.asarray(y)
    info = Compressor.inspect(buf)
    errs = [float(np.max(np.abs(y[i * SIDE:(i + 1) * SIDE].astype(np.float64) - x[i * SIDE:(i + 1) * SIDE])))
            / fr["eb_abs"] for i, fr in enumerate(info["frames"])]
    report("shard compress", x, buf, max(errs), s0, None, f"== chunk_compress (one chip, {r0:.2f} s)")
    report("shard decompress", x, buf, max(errs), d0, None, "out=device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded path over a four-chip mesh")
    args = ap.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (jax backend {jax.default_backend()!r}); nothing run",
              file=sys.stderr)
        return 2
    from repro.launch.jaxcache import enable_compile_cache

    cache = pathlib.Path(enable_compile_cache())
    entries = lambda: sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0  # noqa: E731
    log(f"compile cache: {cache}, {entries()} entries at start")
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if args.chips == 4:
        phase_shard(SEED)
    else:
        x, t = timed(lambda: nyx_field(SIDE, SEED))
        log(f"field: nyx-class lognormal {SIDE}^3 f32, seed {SEED}, {t:.2f} s to generate")
        buf_a = phase_a(x)
        phase_b(x, buf_a)
        phase_c(x)
        phase_d(x)
        phase_e(x)
    log(f"compile cache: {entries()} entries at end")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
