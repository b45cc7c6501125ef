"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip: it makes its inputs from the seed, warms the
cell's programs (set-up), measures whole calls for ``--seconds``, checks
every answer against the configuration's guarantees, and prints one JSON
object as the last line of standard output, after the numbers compared
with their limits as the last lines of standard error. ``--trace 1``
records the benchmark's probes and a device trace in the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result. JAX's persistent compilation cache lives at
``$JAX_COMPILATION_CACHE_DIR`` where that is set, else at
``<checkout>/.jax_cache``; set-up and the check read and write it, and
in the window it is off, so that a program compiled there is compiled
in every run that needs it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from . import harness, probes, readers, registry  # noqa: E402
from . import trace as tr  # noqa: E402

CACHE = registry.CHECKOUT / ".jax_cache"
TRACE_DIR = registry.CHECKOUT / ".bench_trace"


class CompileCounter:
    """Backend compiles and persistent-cache loads while ``on``."""

    def __init__(self):
        self.on, self.compiles, self.loads, self.seconds = False, 0, 0, 0.0

    def event(self, name, *args, **kwargs):
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def duration(self, name, secs, *args, **kwargs):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs


@contextlib.contextmanager
def no_persistent_cache(jax):
    """Neither read nor write JAX's persistent cache inside. A program
    compiled in the window (one whose shapes follow the data of a call) is
    then compiled in every run that needs it, as it is for a user whose
    calls bring new content, and not found in the cache by a later run of
    the same seed; the programs set-up compiled stay in memory."""
    from jax._src import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": peak}


def measure(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    """Set-up, window, check of one cell on whatever devices JAX holds;
    returns the result object (``correct`` ... ``checks``)."""
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_listener(counter.event)
    jax.monitoring.register_event_duration_secs_listener(counter.duration)

    st = harness.setup(cfg, traffic, seed)
    setup_s = time.perf_counter() - t_start

    layer = {m["name"]: registry.metric(m["name"]) for m in cell["per_layer"]} if trace else {}
    rec = probes.Recorder()
    for name in sorted({p for mod in layer.values() for p in mod.PROBES}):
        rec.install(name, registry.probe(name)["target"])
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    counter.on = True
    try:
        t0 = time.perf_counter()
        with no_persistent_cache(jax), jax.profiler.TraceAnnotation(tr.WINDOW):
            w = harness.run_window(st, seconds)
        wall = time.perf_counter() - t0
    finally:
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
        rec.uninstall()
    device = device_info(jax)

    result: dict = {"metrics": {}}
    if trace:
        raw = tr.read_xplane(next(TRACE_DIR.rglob("*.xplane.pb")))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        red = tr.reduce(raw) if raw["devices"] else None  # the CPU backend writes no device plane
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = red["breakdown"]
        run = readers.Run(cfg, wall, rec.self_seconds(), device["kind"], raw, red)
        for m in cell["per_layer"]:
            v = layer[m["name"]].read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(harness.end_to_end(st, w), setup_s=setup_s)
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    diag = {"window_wall_s": wall, "calls_s": [c.seconds for c in w.calls],
            "window_compiles": counter.compiles, "window_compile_s": counter.seconds,
            "window_cache_loads": counter.loads,
            "setup_s": setup_s, "error": w.error}

    t_check = time.perf_counter()
    checks = harness.check(st, w)
    diag["check_s"] = time.perf_counter() - t_check
    print(f"bench: {json.dumps(diag)}", file=sys.stderr)
    result.update(correct=harness.correct(checks, w), attempted=len(w.calls) + w.failed, failed=w.failed,
                  device=device, checks=checks)
    return result


def line(result: dict) -> dict:
    """The result line's keys in order, the numbers compared last."""
    order = ("correct", "attempted", "failed", "metrics", "device", "breakdown", "checks")
    return {k: result[k] for k in order if k in result}


def main(argv=None) -> int:
    args = parse(argv)
    bm = registry.benchmark()
    cell = registry.cell(bm, args.workload)
    cfg, traffic = registry.config(cell["config"]), registry.traffic(cell["traffic"])
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.default_backend() != "tpu" or len(jax.devices()) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX has {len(jax.devices())} "
              f"{jax.default_backend()} device(s). Nothing run.", file=sys.stderr)
        return 2
    sys.path.insert(0, str(registry.CHECKOUT / "src"))
    import repro.core  # noqa: F401  (the system under test: a checkout without it fails here)

    result = measure(cell, cfg, traffic, args.seed, args.seconds, bool(args.trace), T0)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
