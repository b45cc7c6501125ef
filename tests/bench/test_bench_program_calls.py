"""The per-layer metrics that read the program's own call records
(``bench/program_calls.py``): the window's records are picked out of the
process log, each reader computes its share or ratio from them, and
returns ``None`` where there is nothing to read."""
import sys

import pytest

import repro.core
from bench import program_calls, readers, registry
from repro.core import spans

BM = registry.benchmark()
NEW = {"prep_share.write": ("nyx512-cr.write", "nyx512-tp.insitu"),
       "pack_share.write": ("nyx512-cr.write", "nyx512-tp.insitu"),
       "compile_share.write": ("nyx512-cr.write", "nyx512-tp.insitu"),
       "xfer_ratio.write": ("nyx512-cr.write", "nyx512-tp.insitu"),
       "restore_share.read": ("nyx512-cr.read",)}


def record(name, t0, t1, children=(), **counters):
    rec = spans.Record(name, t0, t1)
    for s in children:
        rec.spans.append(spans.Span(*s))
    rec.counters.update(counters)
    return rec


def compress_run():
    """Set-up's warm compress, two window calls and the check's decodes."""
    warm = record("compress", 0.0, 21.0, [("compress.prep", 1.0, 3.0)], in_bytes=100, compile_s=15.0)
    w1 = record("compress", 25.0, 45.0, [("compress.ingest", 25.0, 26.0), ("compress.prep", 26.0, 28.0),
                                         ("compress.scatter", 30.0, 31.0), ("compress.reorder", 31.0, 32.0),
                                         ("compress.pack", 40.0, 40.5)],
                in_bytes=100, h2d_bytes=150, d2h_bytes=50, compile_s=2.0)
    w2 = record("compress", 45.0, 65.0, [("compress.prep", 46.0, 48.0), ("compress.verify", 50.0, 60.0),
                                         ("compress.prep", 51.0, 52.0, 1)],
                in_bytes=100, h2d_bytes=0, d2h_bytes=100, compile_s=1.0)
    check = [record("decompress", 70.0 + 9 * i, 79.0 + 9 * i) for i in range(2)]
    return [warm, w1, w2] + check, (w1, w2)


def test_window_selection_keeps_the_window_calls_only():
    recs, window = compress_run()
    got = program_calls.select(recs, "compress", 41.0)
    assert got == list(window)
    assert program_calls.select(recs, "compress", 100.0)[0] is recs[0]  # a window that holds set-up too
    assert program_calls.select(recs, "missing", 41.0) == []
    reads = [record("decompress", 0.0, 9.0), record("decompress", 10.0, 19.0), record("decompress", 19.0, 28.0)]
    assert program_calls.select(reads, "decompress", 18.5) == reads[1:]


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_loads_and_goes_to_its_cells(name):
    mod = registry.metric(name)
    assert mod.PROBES == () and callable(mod.read)
    cells = {w["name"] for w in BM["workloads"] if name in {m["name"] for m in registry.cell(BM, w["name"])["per_layer"]}}
    assert cells == set(NEW[name])


def run_of(window_s):
    return readers.Run(cfg={}, window_s=window_s, self_s={}, device_kind="TPU v5 lite")


def test_readers_read_the_window_records(monkeypatch):
    recs, _ = compress_run()
    monkeypatch.setattr(spans, "calls", lambda: recs)
    run = run_of(41.0)
    read = {name: registry.metric(name).read(run) for name in NEW if name.endswith(".write")}
    # prep: 1 + 2 (w1) + 2 + 1 (w2: the prep nested in verify counts, and leaves verify's self time) = 6 s
    assert read["prep_share.write"] == pytest.approx(100 * 6.0 / 41.0)
    assert read["pack_share.write"] == pytest.approx(100 * 2.5 / 41.0)
    assert read["compile_share.write"] == pytest.approx(100 * 3.0 / 41.0)
    assert read["xfer_ratio.write"] == pytest.approx(300 / 200)
    reads = [record("decompress", 10.0, 19.0, [("decompress.restore", 12.0, 14.0), ("decompress.scatter", 18.0, 19.0)])]
    monkeypatch.setattr(spans, "calls", lambda: reads)
    assert registry.metric("restore_share.read").read(run_of(9.5)) == pytest.approx(100 * 3.0 / 9.5)


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_return_none_with_no_records(name, monkeypatch):
    monkeypatch.setattr(spans, "calls", lambda: [])
    assert registry.metric(name).read(run_of(30.0)) is None
    monkeypatch.delattr(repro.core, "spans")  # a program without spans
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert registry.metric(name).read(run_of(30.0)) is None
