"""The benchmark's reduction from traces and spans to metrics, on a small
synthetic trace (``trace_small.json``) and on a trace recorded here."""
import json
import pathlib
import sys
import time
import types

import pytest

from bench import probes, readers
from bench import trace as tr

HERE = pathlib.Path(__file__).resolve().parent


@pytest.fixture
def small():
    return json.loads((HERE / "trace_small.json").read_text())


def test_window_busy_and_idle_share(small):
    red = tr.reduce(small)
    assert (red["lo"], red["hi"]) == (0, 1000)
    assert red["window_s"] == pytest.approx(1000e-9)
    # union of [100,400], [600,700], [900,950]: the op after the window is left out
    assert red["busy_s"] == pytest.approx(450e-9)
    run = readers.Run({}, 1.0, {}, "TPU v5 lite", small, red)
    assert readers.device_idle(run) == pytest.approx(55.0)


def test_top_ops_and_kernel_time(small):
    red = tr.reduce(small)
    ops = red["breakdown"]["device_ops"]
    assert [n for n, _ in ops] == ["jit_interp3d_compress:interp3d_compress.1", "jit_f:fusion.1", "jit_g:copy.2"]
    assert ops[0][1] == pytest.approx(250e-9) and ops[1][1] == pytest.approx(150e-9)
    assert tr.kernel_ns(small["devices"], "interp3d", 0, 1000) == (250, 1)
    assert tr.kernel_ns(small["devices"], "nothing", 0, 1000) == (0, 0)
    assert tr.kernel_ns(small["devices"], "jit_interp3d", 0, 1000) == (0, 0)  # the module's name is not the op's


def test_idle_gaps_labelled_by_the_host_probe(small):
    gaps = tr.reduce(small)["breakdown"]["idle_gaps"]
    # [400,600] and [700,900] fall in verify (decode_lossless covers only half of
    # the first), [0,100] and [950,1000] in no probe
    assert [g[0] for g in gaps] == ["verify", "verify", "harness", "harness"]
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 200e-9, 100e-9, 50e-9])


def test_the_window_must_be_marked_once(small):
    small["spans"].append(["window", 2000, 3000])
    with pytest.raises(ValueError, match="one 'bench:window'"):
        tr.reduce(small)


def test_hbm_roofline_from_bytes_and_kernel_time(small):
    red = tr.reduce(small)
    run = readers.Run({}, 1.0, {}, "TPU v5 lite", small, red)
    # 819 bytes at 819 GB/s take 1 ns; the kernel ran 250 ns
    assert readers.hbm_roofline(run, "interp3d", lambda r: 819) == pytest.approx(0.4)
    assert readers.hbm_roofline(run, "absent", lambda r: 819) is None
    no_trace = readers.Run({}, 1.0, {}, "TPU v5 lite")
    assert readers.hbm_roofline(no_trace, "interp3d", lambda r: 819) is None
    assert readers.device_idle(no_trace) is None


def test_unknown_device_has_no_peaks():
    assert readers.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        readers.peaks("TPU v9 imaginary")


@pytest.fixture
def layers(monkeypatch):
    """A stand-in program module with one layer calling another."""
    mod = types.ModuleType("bench_fake_layers")

    def inner(t):
        time.sleep(t)
        return t

    def outer(t):
        time.sleep(t)
        return mod.inner(2 * t)

    mod.inner, mod.outer = inner, outer
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_self_time_of_nested_probes(layers):
    rec = probes.Recorder()
    rec.install("outer", "bench_fake_layers:outer")
    rec.install("inner", "bench_fake_layers:inner")
    try:
        layers.outer(0.01)
        layers.outer(0.01)
    finally:
        rec.uninstall()
    assert layers.outer.__name__ == "outer" and not hasattr(layers.outer, "__wrapped__")
    spans = rec.spans
    assert [s.name for s in spans] == ["outer", "inner", "outer", "inner"]
    assert [s.parent for s in spans] == [-1, 0, -1, 2]
    self_s = rec.self_seconds()
    outer_total = spans[0].seconds + spans[2].seconds
    inner_total = spans[1].seconds + spans[3].seconds
    assert self_s["inner"] == pytest.approx(inner_total)
    assert self_s["outer"] == pytest.approx(outer_total - inner_total)
    assert self_s["outer"] >= 0.02 and self_s["inner"] >= 0.04
    run = readers.Run({}, 1.0, self_s, "TPU v5 lite")
    assert readers.probe_share(run, "outer") == pytest.approx(100 * self_s["outer"])
    assert readers.probe_share(run, "never_installed") is None


def test_probe_spans_land_in_a_recorded_trace(tmp_path, layers):
    import jax

    rec = probes.Recorder()
    rec.install("outer", "bench_fake_layers:outer")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            layers.outer(0.001)
    finally:
        jax.profiler.stop_trace()
        rec.uninstall()
    raw = tr.read_xplane(next(tmp_path.rglob("*.xplane.pb")))
    names = sorted(n for n, _, _ in raw["spans"])
    assert names == ["outer", "window"]
    lo, hi = tr.window(raw)
    (s, e), = [(s, e) for n, s, e in raw["spans"] if n == "outer"]
    assert lo <= s < e <= hi
