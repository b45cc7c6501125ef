"""Lossless hot-path benchmark: MB/s per stage + CR, emitted as JSON.

    PYTHONPATH=src python -m benchmarks.bench_lossless [--out BENCH_lossless.json]
    PYTHONPATH=src python -m benchmarks.bench_lossless --smoke   # tiny CI grid

Measures each lossless stage on a 4 MiB quantization-code-like stream (the
codec's actual workload: Laplacian codes centered on 128) across the
``engine`` dimension (``--engines``: ``numpy`` = the reference host
stages, ``device`` = the jit/Pallas encoding engine of
repro.core.lossless.engine, verified byte-identical before timing) — in
*both* directions: every stage/pipeline/end-to-end row carries decode
columns (``dec_mbps``, and ``dec_dev_mbps`` where a decode twin exists),
with byte-identity between the decode paths asserted before any timing,
sweeps *every registered pipeline* plus the orchestrated ``auto`` mode
over a synthetic byte-stream suite (each row carries a ``pipeline``
dimension with CR + MB/s), sweeps the fixed-steps predictor
configurations plus the
plan-driven ``predictor="auto"`` over a synthetic *field* suite (each row
carries a ``predictor`` dimension; the auto rows record the chosen
PredictorPlan and ``cr_vs_best_fixed``), and times the end-to-end
compressor on a smooth float32 field (after JIT warmup). Each timing is
the best of ``--reps`` runs (timeit-style min-time, which rejects
scheduler noise on shared hosts).

``--devices N`` adds a sharded dimension: an (N, side^3) field compressed
device-parallel through ``repro.core.distributed.shard_compress`` (one
container-v3 frame per device shard) vs the host-sequential chunked
writer, timed and CR-recorded like every other row. When jax initialized
with fewer devices the script re-execs itself once with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (fake CPU devices;
the flag must be set before jax starts).

``--smoke`` shrinks every grid (64 KiB streams, 24^3 fields, 1 rep) so CI
can run the whole script in seconds and upload the JSON as an artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.core import Compressor, CompressorSpec, compression_ratio, cusz_hi_cr, max_abs_err
from repro.core.autotune import fixed_step_baselines
from repro.core.metrics import max_rel_err, psnr, quality_report, value_range
from repro.core.lossless import bitshuffle as bs
from repro.core.lossless import huffman as hf
from repro.core.lossless import orchestrate as orc
from repro.core.lossless import pipelines as pp
from repro.core.lossless import rre, tcms

STREAM_BYTES = 4 << 20
FIELD_SIDE = 64
PRED_FIELD_SIDE = 48  # 27 blocks: the planner samples exhaustively
SMOKE_STREAM_BYTES = 64 << 10
SMOKE_FIELD_SIDE = 24

# The fixed-steps baselines predictor="auto" must match or beat (same
# lossless pipeline, so the comparison isolates the lossy side). Shared
# with tests/test_autotune.py via the importable core/data modules.
FIXED_PREDICTORS = fixed_step_baselines()


def _best(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def quant_code_stream(nbytes: int = STREAM_BYTES, scale: float = 8.0, seed: int = 0) -> np.ndarray:
    """Laplacian uint8 codes centered on 128 — the predictor's output law."""
    rng = np.random.default_rng(seed)
    return np.clip(np.round(rng.laplace(128.0, scale, nbytes)), 0, 255).astype(np.uint8)


def smooth_field(side: int = FIELD_SIDE) -> np.ndarray:
    g = np.stack(np.meshgrid(*[np.linspace(0, 3, side)] * 3, indexing="ij"))
    return (np.sin(g[0] * 2.1) * np.cos(g[1] * 1.7) + 0.5 * np.sin(g[2] * 3.3 + g[0])).astype(np.float32)


def bench_stage(name, enc, dec, data, reps) -> dict:
    payload, hdr = enc(data)
    out = dec(payload, hdr)
    assert np.array_equal(np.asarray(out).view(np.uint8).reshape(-1), data), name
    te = _best(lambda: enc(data), reps)
    td = _best(lambda: dec(payload, hdr), reps)
    nbytes = len(payload) if isinstance(payload, (bytes, bytearray)) else payload.nbytes
    return {
        "stage": name,
        "engine": "numpy",
        "enc_mbps": data.size / te / 1e6,
        "dec_mbps": data.size / td / 1e6,
        "cr": data.size / max(nbytes, 1),
    }


def bench_stage_device(name, enc_dev, dec, data, reps, enc_ref=None, dec_dev=None) -> dict:
    """Engine-dimension twin of bench_stage: the jit/Pallas encode path of
    repro.core.lossless.engine on a device-resident stream. The payload is
    verified byte-identical to the numpy encoder's (the engine contract)
    before timing. ``dec_dev`` times the stage's device decode twin from
    host payload bytes (H2D upload included), verified byte-identical to
    the stream before timing; without one, decode stays on the reference
    path."""
    import jax
    import jax.numpy as jnp

    d = jnp.asarray(data)
    payload, hdr = enc_dev(d)  # also warms the jit caches
    pb = np.asarray(payload).tobytes()
    if enc_ref is not None:  # the contract itself, at bench size
        ref_payload, ref_hdr = enc_ref(data)
        assert pb == ref_payload and hdr == ref_hdr, f"{name}: device != numpy bytes"
    if dec_dev is not None:
        out = dec_dev(pb, hdr)  # warms the decode jit caches
        assert np.array_equal(np.asarray(out).reshape(-1), data), f"{name}: device decode != stream"
        td_fn = lambda: jax.block_until_ready(dec_dev(pb, hdr))  # noqa: E731
    else:
        out = dec(pb, hdr)
        assert np.array_equal(np.asarray(out).view(np.uint8).reshape(-1), data), name
        td_fn = lambda: dec(pb, hdr)  # noqa: E731
    te = _best(lambda: jax.block_until_ready(enc_dev(d)[0]), reps)
    td = _best(td_fn, reps)
    return {
        "stage": name,
        "engine": "device",
        "enc_mbps": data.size / te / 1e6,
        "dec_mbps": data.size / td / 1e6,
        "cr": data.size / max(len(pb), 1),
    }


def synthetic_streams(nbytes: int = STREAM_BYTES) -> dict:
    """The synthetic stream suite: code-stream laws the orchestrator must span."""
    rng = np.random.default_rng(7)
    return {
        "laplace8": quant_code_stream(nbytes, scale=8.0),
        "laplace1": quant_code_stream(nbytes, scale=1.0),
        "runs": np.repeat(rng.integers(126, 131, nbytes // 64, dtype=np.uint8), 64)[:nbytes],
        "sparse": np.where(rng.random(nbytes) < 0.02, rng.integers(0, 256, nbytes), 128).astype(np.uint8),
        "random": rng.integers(0, 256, nbytes, dtype=np.uint8),
    }


def synthetic_fields(side: int = PRED_FIELD_SIDE) -> dict:
    """The synthetic field suite for the predictor dimension: one field per
    regime a spline/scheme/stride choice discriminates (repro.data)."""
    from repro.data import predictor_suite

    return predictor_suite(side)


def sweep_predictors(x: np.ndarray, stream: str, reps: int, eb: float = 1e-3) -> list[dict]:
    """Fixed-steps configs + predictor="auto" on one field; predictor rows."""
    rng = float(x.max() - x.min())
    rows = []

    def case(predictor: str, comp: Compressor) -> dict:
        buf = comp.compress(x)
        y = comp.decompress(buf)
        assert max_abs_err(x, y) <= eb * rng * (1 + 1e-4) + 1e-9, (stream, predictor)
        te = _best(lambda: comp.compress(x), reps)
        td = _best(lambda: comp.decompress(buf), reps)
        return {
            "stage": f"predictor:{predictor}",
            "predictor": predictor,
            "stream": stream,
            "enc_mbps": x.nbytes / te / 1e6,
            "dec_mbps": x.nbytes / td / 1e6,
            "cr": compression_ratio(x, buf),
        }

    for name, cfg in FIXED_PREDICTORS.items():
        rows.append(case(name, Compressor(CompressorSpec(eb=eb, pipeline="cr", autotune=False, **cfg))))
    comp = Compressor(CompressorSpec(eb=eb, predictor="auto", pipeline="cr"))
    row = case("auto", comp)
    row["plan"] = str(comp.last_plan)
    best_fixed = max(r["cr"] for r in rows)
    row["cr_vs_best_fixed"] = row["cr"] / best_fixed
    rows.append(row)
    return rows


def sweep_pipelines(data: np.ndarray, stream: str, reps: int,
                    device: bool = False) -> list[dict]:
    """All registered pipelines + auto on one stream; pipeline dimension rows.
    ``device=True`` adds an ``engine="device"`` row per pipeline: the same
    stream decoded through the stages' decode twins (byte-identity verified
    against the source stream before timing, result on device)."""
    rows = []
    for pipe in sorted(pp.PIPELINES):
        buf = pp.encode(data, pipe)
        assert np.array_equal(pp.decode(buf), data)
        te = _best(lambda: pp.encode(data, pipe), reps)
        td = _best(lambda: pp.decode(buf), reps)
        rows.append(
            {
                "stage": f"pipeline:{pipe}",
                "pipeline": pipe,
                "stream": stream,
                "enc_mbps": data.size / te / 1e6,
                "dec_mbps": data.size / td / 1e6,
                "cr": data.size / len(buf),
            }
        )
        if device:
            import jax
            import jax.numpy as jnp

            dev = jnp.asarray(data)
            dbuf = pp.encode(dev, pipe)  # warms encode jit caches
            assert dbuf == buf, f"{pipe}: device != numpy stream bytes"
            out = pp.decode(buf, device=True)  # warms decode jit caches
            assert np.array_equal(np.asarray(out), data), f"{pipe}: device decode != stream"
            tde = _best(lambda: pp.encode(dev, pipe), reps)
            tdd = _best(lambda: jax.block_until_ready(pp.decode(buf, device=True)), reps)
            rows.append(
                {
                    "stage": f"pipeline:{pipe}",
                    "pipeline": pipe,
                    "engine": "device",
                    "stream": stream,
                    "enc_mbps": data.size / tde / 1e6,
                    "dec_mbps": data.size / tdd / 1e6,
                    "cr": data.size / len(buf),
                }
            )
    buf, record = orc.encode_auto(data)
    assert np.array_equal(pp.decode(buf), data)
    te = _best(lambda: orc.encode_auto(data), reps)
    td = _best(lambda: pp.decode(buf), reps)
    best_fixed = max(r["cr"] for r in rows)
    cr_auto = data.size / len(buf)
    rows.append(
        {
            "stage": "pipeline:auto",
            "pipeline": "auto",
            "stream": stream,
            "picked": record["pipeline"],
            "enc_mbps": data.size / te / 1e6,
            "dec_mbps": data.size / td / 1e6,
            "cr": cr_auto,
            "cr_vs_best_fixed": cr_auto / best_fixed,
        }
    )
    return rows


# The real-fixture spec grid: one abs-mode point (the paper's classic
# regime), the point-wise-relative mode, and the PSNR-target mode — all
# as canonical spec strings, so the bench exercises the same entry point
# (CompressorSpec.from_string) every other consumer uses.
REAL_SPECS = (
    "lossy,rel,1e-3,pipeline=cr,autotune=false",
    "lossy,pw_rel,1e-2,pipeline=cr,autotune=false",
    "lossy,psnr,60,pipeline=cr,autotune=false",
)


def sweep_real_fields(reps: int, smoke: bool, with_metrics: bool) -> list[dict]:
    """The real-field fixture lane: weather/CFD-like structured grids
    (repro.data.realfields, the committed tests/data npz when present)
    swept over the spec-string grid above. Every row verifies its error
    contract before timing — abs bound ≤ header eb, pw_rel max relative
    error ≤ eb, achieved PSNR within 1 dB of target — and (with
    ``--metrics``) carries the full quality_report columns the CI io lane
    gates on."""
    from repro.data import load_real_fields

    rows = []
    for name, field in sorted(load_real_fields().items()):
        if smoke:  # crop, don't subsample: keep the spatial structure
            field = field[tuple(slice(0, min(s, 48 if field.ndim == 2 else 32))
                                for s in field.shape)]
        x = np.ascontiguousarray(field, np.float32)
        rng = value_range(x)
        for spec_str in REAL_SPECS:
            spec = CompressorSpec.from_string(spec_str)
            comp = Compressor(spec)
            buf = comp.compress(x)
            search = (comp.last_telemetry or {}).get("psnr_search")
            y = comp.decompress(buf)
            hdr = Compressor.inspect(buf)
            row = {
                "stage": f"real:{name}", "stream": name, "fixture": "real",
                "spec": spec_str, "value_range": rng, "cr": compression_ratio(x, buf),
            }
            if spec.eb_mode == "pw_rel":
                mre = max_rel_err(x, y)
                assert mre <= spec.eb, (name, spec_str, mre)
                row["eb_rel"] = spec.eb
                row["max_rel_err_vs_eb"] = mre / spec.eb
            else:
                eb_abs = float(hdr["eb_abs"])
                assert max_abs_err(x, y) <= eb_abs * (1 + 1e-4) + 1e-9, (name, spec_str)
                row["eb_abs"] = eb_abs
                if eb_abs > 0:  # the header-implied PSNR floor the gate asserts
                    row["psnr_floor"] = 20.0 * np.log10(rng / eb_abs)
            if spec.psnr_target is not None:
                achieved = psnr(x, y)
                assert achieved >= spec.psnr_target - 1.0, (name, achieved)
                row["psnr_target"] = spec.psnr_target
                if search:
                    row["psnr_search_trials"] = search["trials"]
            if with_metrics:
                row.update(quality_report(x, y, buf))
            te = _best(lambda: comp.compress(x), reps)
            td = _best(lambda: comp.decompress(buf), reps)
            row["enc_mbps"] = x.nbytes / te / 1e6
            row["dec_mbps"] = x.nbytes / td / 1e6
            rows.append(row)
    return rows


def sweep_sharded(devices: int, side: int, reps: int, eb: float = 1e-3) -> list[dict]:
    """Device-parallel shard_compress vs the host-sequential chunked writer
    on an (devices, side^3) field; one row per writer, pipeline=cr."""
    import jax

    from repro.core import chunk_compress, shard_compress, shard_decompress

    base = smooth_field(side)
    x = np.stack([(base * (1 + 0.05 * i)).astype(np.float32) for i in range(devices)])
    spec = CompressorSpec(eb=eb, pipeline="cr", autotune=False)
    buf = shard_compress(x, spec=spec)
    y = shard_decompress(buf)
    rng = float(x.max() - x.min())
    assert max_abs_err(x, y) <= eb * rng * (1 + 1e-5) + 1e-9
    cbuf = chunk_compress(x, n_chunks=devices, spec=spec)  # its own bytes: a
    # chunk-writer size regression must not hide behind the sharded row's CR
    te = _best(lambda: shard_compress(x, spec=spec), reps)
    td = _best(lambda: shard_decompress(buf, workers=devices), reps)
    tc = _best(lambda: chunk_compress(x, n_chunks=devices, spec=spec), reps)
    common = {"pipeline": "cr", "devices": devices,
              "jax_devices": jax.device_count(), "n_frames": devices}
    return [
        dict(common, stage=f"shard_compress:{devices}dev", stream=f"sharded-{devices}dev",
             cr=x.nbytes / len(buf),
             enc_mbps=x.nbytes / te / 1e6, dec_mbps=x.nbytes / td / 1e6),
        dict(common, stage=f"chunk_compress:{devices}dev", stream=f"chunked-{devices}dev",
             cr=x.nbytes / len(cbuf),
             enc_mbps=x.nbytes / tc / 1e6,
             dec_mbps=x.nbytes / _best(lambda: shard_decompress(cbuf), reps) / 1e6),
    ]


def run(reps: int = 5, smoke: bool = False, devices: int = 1,
        engines: tuple = ("numpy", "device"), fixture: str = "synthetic",
        with_metrics: bool = False) -> dict:
    stream_bytes = SMOKE_STREAM_BYTES if smoke else STREAM_BYTES
    if fixture == "real":
        # the quality lane: real-field fixtures only, spec-string grid,
        # metric columns — a separate JSON shape from the hot-path grid
        return {
            "bench": "real_fields",
            "smoke": bool(smoke),
            "fixture": "real",
            "metrics": bool(with_metrics),
            "specs": list(REAL_SPECS),
            "timing": f"best of {reps} reps after warmup",
            "stages": sweep_real_fields(reps, smoke, with_metrics),
        }
    field_side = SMOKE_FIELD_SIDE if smoke else FIELD_SIDE
    pred_side = SMOKE_FIELD_SIDE if smoke else PRED_FIELD_SIDE
    data = quant_code_stream(stream_bytes)
    rows = []
    if "numpy" in engines:
        rows += [
            bench_stage("hf", hf.encode, hf.decode, data, reps),
            bench_stage("rre4", lambda d: rre.rre_encode(d, 4), rre.rre_decode, data, reps),
            bench_stage("rze1", lambda d: rre.rze_encode(d, 1), rre.rze_decode, data, reps),
            bench_stage("tcms8", lambda d: tcms.tcms_encode(d, 8), tcms.tcms_decode, data, reps),
            bench_stage("bit1", bs.bitshuffle_encode, bs.bitshuffle_decode, data, reps),
        ]
    if "device" in engines:
        from repro.core.lossless import engine as eng

        rows += [
            bench_stage_device("hf", eng.hf_encode_device, hf.decode, data, reps,
                               enc_ref=hf.encode, dec_dev=eng.hf_decode_device),
            bench_stage_device("rre4", lambda d: eng.rre_encode_device(d, 4), rre.rre_decode, data, reps,
                               enc_ref=lambda d: rre.rre_encode(d, 4), dec_dev=eng.rre_decode_device),
            bench_stage_device("rze1", lambda d: eng.rze_encode_device(d, 1), rre.rze_decode, data, reps,
                               enc_ref=lambda d: rre.rze_encode(d, 1), dec_dev=eng.rze_decode_device),
            bench_stage_device("tcms8", lambda d: eng.tcms_encode_device(d, 8), tcms.tcms_decode, data, reps,
                               enc_ref=lambda d: tcms.tcms_encode(d, 8), dec_dev=eng.tcms_decode_device),
            bench_stage_device("bit1", eng.bit1_encode_device, bs.bitshuffle_decode, data, reps,
                               enc_ref=bs.bitshuffle_encode, dec_dev=eng.bit1_decode_device),
        ]
    for stream, sdata in synthetic_streams(stream_bytes).items():
        rows.extend(sweep_pipelines(sdata, stream, reps, device="device" in engines))
    for stream, field in synthetic_fields(pred_side).items():
        rows.extend(sweep_predictors(field, stream, reps))
    if devices > 1:
        rows.extend(sweep_sharded(devices, field_side, reps))
    # end-to-end compressor on a smooth field, warmed up (JIT + caches)
    x = smooth_field(field_side)
    comp = cusz_hi_cr(eb=1e-3)
    buf = comp.compress(x)
    y = comp.decompress(buf)
    rng = float(x.max() - x.min())
    assert max_abs_err(x, y) <= 1e-3 * rng * (1 + 1e-5) + 1e-9
    tc = _best(lambda: comp.compress(x), reps)
    td = _best(lambda: comp.decompress(buf), reps)
    rows.append(
        {
            "stage": f"cusz_hi_cr:{field_side}^3",
            "enc_mbps": x.nbytes / tc / 1e6,
            "dec_mbps": x.nbytes / td / 1e6,
            "compress_seconds": tc,
            "decompress_seconds": td,
            "cr": compression_ratio(x, buf),
        }
    )
    # verify-mode overhead: the same end-to-end encode under the runtime
    # bound-verification ladder. The container must be byte-identical in
    # every mode (verification is read-only on a clean encode); the CI
    # gate caps the verify=sample overhead so the default-on guarantee
    # stays cheap.
    t_off = None
    for vmode in ("off", "sample", "full"):
        vcomp = Compressor(CompressorSpec(eb=1e-3, pipeline="cr", autotune=False, verify=vmode))
        vbuf = vcomp.compress(x)
        if vmode == "off":
            base_buf = vbuf
        else:
            assert vbuf == base_buf, f"verify={vmode} changed the container bytes"
        tv = _best(lambda: vcomp.compress(x), reps)
        t_off = tv if t_off is None else t_off
        rows.append(
            {
                "stage": f"verify:{vmode}",
                "verify": vmode,
                "enc_mbps": x.nbytes / tv / 1e6,
                "dec_mbps": x.nbytes / td / 1e6,
                "cr": compression_ratio(x, vbuf),
                "verify_overhead_pct": max(0.0, (tv / t_off - 1.0) * 100.0),
            }
        )
    if "device" in engines:
        # end-to-end decompress-onto-device: decode twins + device
        # reconstruct, result left on device (bit-identity verified)
        import jax

        yd = comp.decompress(buf, out="device")  # warms jit caches
        assert np.array_equal(np.asarray(yd), y), "device decompress != numpy"
        tdd = _best(lambda: jax.block_until_ready(comp.decompress(buf, out="device")), reps)
        rows.append(
            {
                "stage": f"cusz_hi_cr:{field_side}^3",
                "engine": "device",
                "enc_mbps": x.nbytes / tc / 1e6,
                "dec_mbps": x.nbytes / tdd / 1e6,
                "decompress_seconds": tdd,
                "cr": compression_ratio(x, buf),
            }
        )
    return {
        "bench": "lossless_hot_path",
        "smoke": bool(smoke),
        "devices": int(devices),
        "engines": list(engines),
        "stream_bytes": stream_bytes,
        "field": f"{field_side}^3 float32, eb=1e-3 rel",
        "pred_field": f"{pred_side}^3 float32, eb=1e-3 rel, pipeline=cr",
        "timing": f"best of {reps} reps after warmup",
        "stages": rows,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_lossless.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI: 64 KiB streams, 24^3 fields, 1 rep")
    ap.add_argument("--devices", type=int, default=1,
                    help="sharded dimension: shard_compress over N (fake CPU) devices")
    ap.add_argument("--engines", default="numpy,device",
                    help="comma-separated lossless-engine dimension to sweep "
                         "over the stage benches (numpy = reference host "
                         "stages, device = jit/Pallas engine)")
    ap.add_argument("--fixture", default="synthetic", choices=("synthetic", "real"),
                    help="real = the weather/CFD fixture lane (spec-string "
                         "grid incl. pw_rel + psnr_target, quality columns)")
    ap.add_argument("--metrics", action="store_true",
                    help="record quality_report columns (psnr/ssim/spectral "
                         "error/...) on every real-fixture row")
    args = ap.parse_args(argv)
    engines = tuple(e.strip() for e in args.engines.split(",") if e.strip())
    for e in engines:
        if e not in ("numpy", "device"):
            ap.error(f"unknown engine {e!r}; choose from numpy,device")
    if args.smoke:
        args.reps = min(args.reps, 1)
    if args.devices > 1 and os.environ.get("_BENCH_REEXEC") != "1":
        # the device count must be fixed before jax initializes, and this
        # parent must never initialize jax itself (on an accelerator host it
        # would hold the chip its child needs): decide from argv and the
        # environment alone, and re-exec once. XLA honours the LAST
        # occurrence of a repeated flag, so inherited device-count overrides
        # are stripped, not merely prepended-around.
        inherited = [f for f in os.environ.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform_device_count")]
        env = dict(os.environ, _BENCH_REEXEC="1",
                   XLA_FLAGS=" ".join([f"--xla_force_host_platform_device_count={args.devices}"]
                                      + inherited))
        return subprocess.run([sys.executable, os.path.abspath(__file__)]
                              + (argv if argv is not None else sys.argv[1:]), env=env).returncode
    from repro.launch.jaxcache import enable_compile_cache

    enable_compile_cache()
    result = run(args.reps, smoke=args.smoke, devices=args.devices, engines=engines,
                 fixture=args.fixture, with_metrics=args.metrics)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    for r in result["stages"]:
        tag = r["stage"] + (f"[{r['stream']}]" if "stream" in r and "fixture" not in r else "")
        if "engine" in r:
            tag += f"({r['engine']})"
        if "spec" in r:
            tag += f"[{r['spec'].split(',pipeline')[0]}]"
        picked = f"  -> {r['picked']}" if "picked" in r else ""
        if "plan" in r:
            picked = f"  -> {r['plan']}  (x{r['cr_vs_best_fixed']:.3f} vs best fixed)"
        if "psnr" in r:
            picked += f"  PSNR {r['psnr']:6.2f} dB  SSIM {r['ssim']:.4f}  spec_err {r['spectral_error']:.4f}"
        print(
            f"{tag:44s} enc {r['enc_mbps']:8.1f} MB/s   dec {r['dec_mbps']:8.1f} MB/s   CR {r['cr']:8.2f}{picked}"
        )
    print(f"-> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
