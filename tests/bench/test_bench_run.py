"""The entry point: it refuses to run without a chip, and its result line
has the contract's keys in order, the numbers compared last."""
import copy
import json
import os
import subprocess
import sys
import time

from bench import registry, run

BM = registry.benchmark()


def test_no_chip_exits_2_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload", "nyx512-cr.write", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=registry.CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_result_line_schema():
    workload = "nyx512-cr.read"
    cell = registry.cell(BM, workload)
    cfg = copy.deepcopy(registry.config(cell["config"]))
    cfg["field"]["shape"] = [32, 32, 32]
    traffic = registry.traffic(cell["traffic"])
    for trace, want in ((0, cell["end_to_end"]), (1, cell["per_layer"])):
        res = run.measure(cell, cfg, traffic, 2**31 + 3, 0.3, bool(trace), time.perf_counter())
        line = json.loads(json.dumps(run.line(res)))
        keys = list(line)
        assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"] and keys[-1] == "checks"
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
        assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
        units = {m["name"]: m["unit"] for m in want}
        # the CPU backend writes no device plane, so device-trace metrics are left out there
        reported = {n: m["unit"] for n, m in line["metrics"].items()}
        assert reported and set(reported) <= set(units), (reported, units)
        assert all(units[n] == u for n, u in reported.items())
        if trace == 0:
            assert set(reported) == set(units)
            assert line["metrics"]["setup_s"]["value"] > 0
        for c in line["checks"].values():
            assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
