"""The check that decides ``correct``: sound runs pass it, and the control
and every fault a cell can have fail it.

Each cell's harness runs here on the CPU at 32^3 (set-up, window, check;
only the look for a chip is skipped), with the timed path broken
underneath: a call that returns stale state, half of the field left out
and filled with the mean of the rest, an answer altered where it is
produced. One chip has no exchange between chips to leave out. The
control is the plain reference codec computed in bfloat16, put in the
program's place.
"""
import copy

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, reference, registry

SIDE = 32
SEED = 2**31 + 5  # past 32 signed bits, as the benchmark's seeds are
BM = registry.benchmark()


def tiny(workload):
    cell = registry.cell(BM, workload)
    cfg = copy.deepcopy(registry.config(cell["config"]))
    cfg["field"]["shape"] = [SIDE] * 3
    return cfg, registry.traffic(cell["traffic"])


CELLS = [w["name"] for w in BM["workloads"]]


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    cfg, traffic = tiny(request.param)
    return harness.setup(cfg, traffic, SEED)


@pytest.fixture(params=CELLS)
def workload(request):
    return request.param


def verdict(st, seconds=0.0):  # one call: the window always makes at least one
    w = harness.run_window(st, seconds)
    checks = harness.check(st, w)
    return harness.correct(checks, w), checks, w


def test_sound_run_is_correct(cell):
    ok, checks, w = verdict(cell)
    assert ok, checks
    assert w.calls and w.failed == 0
    assert checks["max_err_over_bound"]["value"] <= 1.0 + 1e-4
    assert checks["cpu_decode_diff_points"]["value"] == 0


def entry(st):
    return "compress" if st.traffic["entry"] == "compress" else "decompress"


def test_stale_answer_is_refused(cell, monkeypatch):
    """The call returns what it returned before (its state unchanged)."""
    if entry(cell) == "compress":
        # the window's call gets the container of the snapshot set-up compressed
        stale = cell.comp.compress(cell.snapshot(0))
        monkeypatch.setattr(cell.comp, "compress", lambda x: stale)
    else:
        shape = tuple(cell.cfg["field"]["shape"])
        monkeypatch.setattr(cell.comp, "decompress", lambda buf, out="numpy": np.zeros(shape, np.float32))
    ok, checks, _ = verdict(cell)
    assert not ok and checks["max_err_over_bound"]["value"] > 2, checks


def test_half_left_out_is_refused(cell, monkeypatch):
    """Half of the points left out, the mean of the rest in their place."""
    def halve(a):
        a = np.array(a, np.float32)
        flat = a.reshape(-1)
        flat[flat.size // 2:] = flat[: flat.size // 2].mean()
        return a

    if entry(cell) == "compress":
        real = cell.comp.compress
        monkeypatch.setattr(cell.comp, "compress", lambda x: real(halve(x)))
    else:
        real = cell.comp.decompress
        monkeypatch.setattr(cell.comp, "decompress", lambda buf, out="numpy": halve(real(buf, out=out)))
    ok, checks, _ = verdict(cell)
    assert not ok and checks["max_err_over_bound"]["value"] > 2, checks


def test_altered_answer_is_refused(cell, monkeypatch):
    """One quantization code (compress) or one decoded point (decompress)
    altered where it is produced."""
    if entry(cell) == "compress":
        from repro.core.compressor import Compressor

        real = Compressor._run_predictor

        def altered(self, *args, **kwargs):
            codes, outl = real(self, *args, **kwargs)
            codes = np.array(codes)
            c = codes[0, 5, 6, 7]  # inside block 0, off the anchor lattice, so the block owns it
            assert c != 0, "an outlier; pick another point"
            codes[0, 5, 6, 7] = c + 1 if c < 255 else c - 1
            return codes, outl

        monkeypatch.setattr(Compressor, "_run_predictor", altered)
        monkeypatch.setattr(cell.comp, "spec", cell.comp.spec.__class__(**dict(cell.cfg["spec"], verify="off")))
    else:
        real = cell.comp.decompress
        x = cell.host_snapshot(0)
        eb = reference.bound_abs(float(x.min()), float(x.max()), cell.cfg)

        def altered(buf, out="numpy"):
            y = np.array(real(buf, out=out))
            y.reshape(-1)[y.size // 3] += np.float32(3 * eb)
            return y

        monkeypatch.setattr(cell.comp, "decompress", altered)
    ok, checks, w = verdict(cell)
    assert not ok, checks
    assert checks["max_err_over_bound"]["value"] > 1.5 or checks["cpu_decode_diff_points"]["value"] > 0, checks


@pytest.mark.parametrize("dtype, sound", [(jnp.float32, True), (jnp.bfloat16, False)], ids=["f32", "bf16"])
def test_control_is_refused(workload, dtype, sound):
    """The plain reference codec in the program's place, through the same
    set-up, window and check as a run: sound in float32, refused in
    bfloat16 (the control)."""
    cfg, _ = tiny(workload)
    v = control.verdict(workload, SEED, dtype, cfg)
    assert v["correct"] is sound, v
    worst = v["checks"]["max_err_over_bound"]["value"]
    assert worst <= 1.0 + 1e-4 if sound else worst > 3, v
    assert v["checks"]["cpu_decode_diff_points"]["value"] == 0


def test_no_answer_is_not_correct():
    w = harness.Window([], failed=1, error="boom")
    cfg, traffic = tiny("nyx512-cr.write")
    st = harness.State(cfg, traffic, SEED, None, None, None, None, None)
    checks = harness.check(st, w)
    assert not harness.correct(checks, w)


def test_a_read_into_device_arrays_is_correct():
    """The decompress entry's other output, ``out="device"``: each call ends
    when its device array is ready, and the check reads it back."""
    cfg, traffic = tiny("nyx512-cr.read")
    st = harness.setup(cfg, dict(traffic, out="device"), SEED)
    ok, checks, w = verdict(st)
    assert ok, checks
    assert not isinstance(w.calls[0].answer, np.ndarray)
