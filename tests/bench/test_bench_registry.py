"""BENCHMARK.json against the benchmark's contract, and discovery of every
part by name: a new configuration, traffic mix, metric or probe is a new
file, found with no edit."""
import json
import math
import re
import shutil

import pytest

from bench import kernels, registry

ROOT = registry.CHECKOUT
BM = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\n\t]{1,200}$")


def test_benchmark_json_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BM["command"]) <= 32 and all(TEXT.match(w) for w in BM["command"])
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p and not p.startswith("/")
        assert (ROOT / p).is_dir()
    groups = ("configs", "workloads", "end_to_end", "per_layer")
    for g in groups:
        names = [e["name"] for e in BM[g]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names), g
    metric_names = [m["name"] for g in ("end_to_end", "per_layer") for m in BM[g]]
    assert len(metric_names) == len(set(metric_names))


def test_configs_and_cells():
    configs = {c["name"]: c for c in BM["configs"]}
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BM["paths"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert registry.config(c["name"]) == json.loads((ROOT / c["file"]).read_text())
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert TEXT.match(c["why"]) and TEXT.match(c["source"])
    used = set()
    pairs = set()
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4) and TEXT.match(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        registry.traffic(w["traffic"])
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and m["better"] in ("lower", "higher")
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert UNIT.match(m["unit"]) and TEXT.match(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in cells:
        cell = registry.cell(BM, w)
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2, w
        assert cell["per_layer"], w
        for m in cell["per_layer"]:  # a per-layer metric moves a metric its cells report
            assert m["moves"] in names, (w, m["name"])


def test_every_metric_has_a_reader_and_its_probes_resolve():
    import importlib

    for m in BM["per_layer"]:
        mod = registry.metric(m["name"])
        assert callable(mod.read)
        for p in mod.PROBES:
            target = registry.probe(p)["target"]
            modname, _, attrs = target.partition(":")
            obj = importlib.import_module(modname)
            for a in attrs.split("."):
                obj = getattr(obj, a)
            assert callable(obj), target


def test_a_new_file_is_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(registry.HERE, root, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = registry.config("nyx512-cr", root)
    cfg["name"] = "nyx256-cr"
    cfg["field"]["shape"] = [256, 256, 256]
    (root / "configs" / "nyx256-cr.json").write_text(json.dumps(cfg))
    (root / "traffic" / "burst.json").write_text(json.dumps({"entry": "compress", "note": "back to back"}))
    (root / "probes" / "pack.json").write_text(json.dumps({"target": "repro.core.compressor:Compressor._pack_interp"}))
    (root / "metrics" / "pack_share.write.py").write_text(
        "from bench.readers import probe_share\nPROBES = ('pack',)\n\n\ndef read(run):\n    return probe_share(run, 'pack')\n")
    assert registry.config("nyx256-cr", root)["field"]["shape"] == [256, 256, 256]
    assert registry.traffic("burst", root)["note"] == "back to back"
    assert registry.probe("pack", root)["target"].endswith("_pack_interp")
    assert registry.metric("pack_share.write", root).PROBES == ("pack",)
    assert "nyx256-cr" in registry.names("configs", root) and "pack_share.write" in registry.names("metrics", root)
    with pytest.raises(KeyError, match="no configs named 'nyx1024-cr'"):
        registry.config("nyx1024-cr", root)
    with pytest.raises(ValueError, match="bad traffic name"):
        registry.traffic("../configs/nyx512-cr", root)


RAMP = '''"""A ramp field, shifted by the snapshot's index."""
import jax.numpy as jnp


def base(field):
    n = 1
    for s in field["shape"]:
        n *= s
    return jnp.linspace(0.0, 1.0, n, dtype=jnp.float32).reshape(field["shape"])


def snapshot(b, field, seed, k):
    return jnp.roll(b, k + seed % 7, axis=0)
'''

ROUNDTRIP = '''"""Each call compresses a new snapshot and decodes it again."""
import time

from bench.harness import Call

KEYS = {"out": ("numpy",)}


def setup(st):
    st.comp.decompress(st.comp.compress(st.snapshot(0)))


def call(st, i):
    x = st.snapshot(i + 1)
    t0 = time.perf_counter()
    buf = st.comp.compress(x)
    y = st.comp.decompress(buf, out=st.traffic["out"])
    return Call(int(x.nbytes), len(buf), time.perf_counter() - t0, (buf, y), i + 1)


def answers(st, w):
    for c in w.calls:
        yield c.source, c.answer[1]


def witness(st, w):
    return w.calls[0].answer[0], [0]


def end_to_end(st, w):
    return {"roundtrip_MBps": sum(c.nbytes_in for c in w.calls) / w.seconds / 1e6}
'''


def test_a_new_generator_and_entry_drive_a_run(tmp_path):
    """A field generator and an entry driver added as files of their own
    run a whole set-up, window and check, with no file edited."""
    from bench import harness

    root = tmp_path / "bench"
    shutil.copytree(registry.HERE, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "generators" / "ramp.py").write_text(RAMP)
    (root / "entries" / "roundtrip.py").write_text(ROUNDTRIP)
    assert "ramp" in registry.names("generators", root) and "roundtrip" in registry.names("entries", root)
    cfg = registry.config("nyx512-cr", root)
    cfg["field"] = {"generator": "ramp", "shape": [32, 32, 32]}
    st = harness.setup(cfg, {"entry": "roundtrip", "out": "numpy"}, 2**40 + 1, root=root)
    w = harness.run_window(st, 0.0)
    checks = harness.check(st, w)
    assert harness.correct(checks, w), checks
    assert set(harness.end_to_end(st, w)) == {"roundtrip_MBps"}


@pytest.mark.parametrize("traffic, why", [
    ({"entry": "compress", "loop": "open"}, "takes the keys"),
    ({"entry": "compress", "clients": 4}, "takes the keys"),
    ({"entry": "decompress"}, "takes the keys"),
    ({"entry": "decompress", "out": "gpu"}, "takes one of"),
])
def test_a_traffic_key_the_entry_does_not_take_is_refused(traffic, why):
    from bench import harness

    with pytest.raises(ValueError, match=why):
        harness.validate(traffic, registry.entry(traffic["entry"]))


def test_every_traffic_mix_is_one_its_entry_takes():
    from bench import harness

    for name in registry.names("traffic"):
        t = registry.traffic(name)
        harness.validate(t, registry.entry(t["entry"]))
    for name in registry.names("generators"):
        assert callable(registry.generator(name).snapshot)


def test_a_per_layer_metric_without_workloads_follows_what_it_moves():
    bm = json.loads(json.dumps(BM))
    bm["per_layer"].append({"name": "x_share", "unit": "%", "better": "lower", "source": "program_span",
                            "layer": "X", "moves": "decompress_MBps"})
    assert "x_share" in {m["name"] for m in registry.cell(bm, "nyx512-cr.read")["per_layer"]}
    assert "x_share" not in {m["name"] for m in registry.cell(bm, "nyx512-cr.write")["per_layer"]}


@pytest.mark.parametrize("shape", [(32, 32, 32), (33, 17, 40), (2, 24, 24, 24), (100, 500, 500)])
def test_block_count_matches_the_program(shape):
    from repro.core import blocks as blk

    padded = blk.padded_shape(shape[-3:], blk.ANCHOR_STRIDE)
    batch = math.prod(shape[:-3])
    assert kernels.blocks_of(shape) == batch * math.prod((p - 1) // blk.ANCHOR_STRIDE for p in padded)


def test_interp3d_bytes_at_512_cubed():
    nb = kernels.blocks_of((512, 512, 512))
    assert nb == 32 ** 3
    # 32768 blocks of 17^3 points: f32 in, int32 codes and f32 reconstruction out
    assert kernels.interp3d_bytes(nb) == 32768 * 4913 * 12 == 1_931_870_208
    assert kernels.interp3d_bytes(1) == 128 * 4913 * 12  # padded to a whole 128-lane grid step
