"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table4,...]

Prints ``name,us_per_call,derived`` CSV rows per case (derived carries the
table-specific metric: CR / PSNR / GiB/s / roofline terms)."""
from __future__ import annotations

import argparse
import sys
import time


def _emit(rows):
    for r in rows:
        name = ":".join(str(r.get(k)) for k in ("table", "dataset", "arch", "shape", "compressor", "variant", "eb") if r.get(k) is not None)
        us = r.get("comp_us", r.get("us", 0.0))
        derived = {k: v for k, v in r.items() if k not in ("table", "dataset", "arch", "shape", "compressor", "variant", "eb", "comp_us")}
        print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="full-size datasets (slow)")
    ap.add_argument("--data-dir", default=None, help="real SDRBench files if available")
    ap.add_argument("--only", default="", help="comma list: table4,fig8,fig10,table5,table1,roofline")
    args = ap.parse_args(argv)
    from repro.launch.jaxcache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    from . import fig8_rate_distortion, fig10_throughput, roofline, table1_residual, table4_cr, table5_ablation

    jobs = {
        "table4": lambda: table4_cr.run(full=args.full, data_dir=args.data_dir),
        "fig8": lambda: fig8_rate_distortion.run(full=args.full, data_dir=args.data_dir),
        "fig10": lambda: fig10_throughput.run(full=args.full, data_dir=args.data_dir),
        "table5": lambda: table5_ablation.run(full=args.full, data_dir=args.data_dir),
        "table1": lambda: table1_residual.run(full=args.full, data_dir=args.data_dir),
        "roofline": roofline.run,
    }
    for name, job in jobs.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = job()
            _emit(rows)
            print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            print(f"# {name}: FAILED {type(e).__name__}: {e}")
            raise
    return 0


if __name__ == "__main__":
    sys.exit(main())
