"""Find the benchmark's parts by name: a configuration, field generator,
traffic mix, entry driver, per-layer metric or probe is one file, so
adding one edits nothing.

Every lookup takes the directory that holds ``configs/``,
``generators/``, ``traffic/``, ``entries/``, ``metrics/`` and ``probes/``
(this package by default), which lets the tests point the harness at a
copy with files of their own.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _path(root: pathlib.Path, kind: str, name: str, suffix: str) -> pathlib.Path:
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = pathlib.Path(root) / kind / f"{name}{suffix}"
    if not path.is_file():
        raise KeyError(f"no {kind} named {name!r} in {path.parent} (have: {', '.join(names(kind, root))})")
    return path


CODE = ("metrics", "generators", "entries")  # kinds that are Python modules; the rest are JSON


def names(kind: str, root=HERE) -> list[str]:
    suffix = ".py" if kind in CODE else ".json"
    return sorted(p.name[: -len(suffix)] for p in (pathlib.Path(root) / kind).glob(f"*{suffix}")
                  if not p.name.startswith("_"))


def config(name: str, root=HERE) -> dict:
    return json.loads(_path(root, "configs", name, ".json").read_text())


def traffic(name: str, root=HERE) -> dict:
    return json.loads(_path(root, "traffic", name, ".json").read_text())


def probe(name: str, root=HERE) -> dict:
    return json.loads(_path(root, "probes", name, ".json").read_text())


def _module(root, kind: str, name: str):
    path = _path(root, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str, root=HERE):
    """The reader module of a per-layer metric: ``PROBES`` names the probes
    it reads, ``read(run)`` returns its value or ``None`` where the run
    holds nothing for it to read."""
    return _module(root, "metrics", name)


def generator(name: str, root=HERE):
    """A field generator: ``base(field)`` makes what every snapshot is cut
    from, on the device, from the configuration's ``field`` entry;
    ``snapshot(base, field, seed, k)`` makes snapshot ``k`` of a run's seed."""
    return _module(root, "generators", name)


def entry(name: str, root=HERE):
    """An entry driver: the program call a traffic mix's window drives
    (see ``bench/harness.py`` for what it defines)."""
    return _module(root, "entries", name)


def benchmark(path=CHECKOUT / "BENCHMARK.json") -> dict:
    return json.loads(pathlib.Path(path).read_text())


def cell(bm: dict, workload: str) -> dict:
    """The workload entry with the metrics it reports: the ``end_to_end``
    entries whose ``workloads`` include it (absent: every cell), and the
    ``per_layer`` entries whose ``workloads`` include it (absent: every
    cell that reports the end-to-end metric the entry moves)."""
    for w in bm["workloads"]:
        if w["name"] == workload:
            e2e = [m for m in bm["end_to_end"] if workload in m.get("workloads", [workload])]
            mine = {m["name"] for m in e2e}
            layer = [m for m in bm["per_layer"]
                     if workload in m.get("workloads", ()) or ("workloads" not in m and m["moves"] in mine)]
            return dict(w, end_to_end=e2e, per_layer=layer)
    raise KeyError(f"no workload {workload!r}; have {', '.join(w['name'] for w in bm['workloads'])}")
