"""One run of one cell: set-up, the measured window, the check.

A cell's traffic mix (``traffic/<name>.json``) names under ``"entry"`` the
entry driver (``entries/<name>.py``) whose program call the window
drives. Every other key of the mix is one that driver declares in
``KEYS``, with a value it lists there; anything else is refused, so a mix
never runs as something it does not say. ``"note"`` is free text.

An entry driver defines:

- ``KEYS``: ``{key: (allowed values, ...)}``, every key required;
- ``setup(st)``: the warm-up, counted in ``setup_s``;
- ``call(st, i) -> Call``: the window's ``i``-th call, timed around the
  program call alone and ended by its answer on the host or, for a device
  answer, by the answer being ready;
- ``answers(st, w)``: ``(snapshot, decoded array or None)`` for every
  answer of the window;
- ``witness(st, w)``: a container, and the window's answers (by index)
  whose decode the host CPU's decode of that container must equal;
- ``end_to_end(st, w)``: the end-to-end values the window gives.

The configuration's ``field`` names a generator (``generators/<name>.py``)
that makes snapshot ``k`` of the run's seed on the device; ``"input"``
says whether the program is handed it there (``"device"``) or as a host
array (``"host"``). A snapshot is made when a call needs it, outside the
call's time, and made again for the check.

The window starts calls until ``seconds`` have passed (at least one) and
lets the last one finish, so it holds whole calls only; the end-to-end
rates count all the work and all the time of those calls.

The check comes after the window, judges every answer the window
produced against the configuration's guarantees, and is not timed:

- ``max_err_over_bound``: the worst ``|decoded - input|`` over every
  point of every answer, over the bound the benchmark works out itself
  from the configuration and the input's range (never read from a
  container); limit ``1 + bound_slack``.
- ``cpu_decode_diff_points``: points at which the host CPU's decode of
  the witness container differs from the chip's decodes of it; limit 0.

The program the harness drives is ``bench/program.py``; the control
(``bench/control.py``) takes its place with the same two functions.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import sys
import time

import numpy as np

from . import program as system_under_test
from . import reference, registry

FREE_KEYS = ("entry", "note")
INPUTS = ("host", "device")


@dataclasses.dataclass
class Call:
    nbytes_in: int
    nbytes_out: int
    seconds: float
    answer: object  # a container (compress) or a decoded array (decompress)
    source: int     # the snapshot the call worked on


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    seed: int
    program: object  # compressor(cfg) and decode_on_cpu(cfg, buf)
    comp: object     # the compressor the window drives
    entry: object    # the entry driver module
    gen: object      # the field generator module
    base: object     # what the generator cuts every snapshot from, on the device
    container: bytes | None = None  # what set-up wrote, for an entry that reads

    def snapshot(self, k: int):
        """Snapshot ``k`` as the program is handed it, ready."""
        x = self.gen.snapshot(self.base, self.cfg["field"], self.seed, k)
        if self.cfg["input"] == "host":
            return np.asarray(x)
        return x.block_until_ready()

    def host_snapshot(self, k: int) -> np.ndarray:
        return np.asarray(self.gen.snapshot(self.base, self.cfg["field"], self.seed, k))


@dataclasses.dataclass
class Window:
    calls: list
    failed: int = 0
    error: str = ""

    @property
    def seconds(self) -> float:
        return sum(c.seconds for c in self.calls)


def validate(traffic: dict, entry) -> None:
    """Refuse a mix whose keys or values its entry driver does not take."""
    keys = set(traffic) - set(FREE_KEYS)
    if keys != set(entry.KEYS):
        raise ValueError(f"traffic for entry {traffic['entry']!r} takes the keys {sorted(entry.KEYS)}"
                         f" (and {', '.join(FREE_KEYS)}), not {sorted(keys)}")
    for k in keys:
        if traffic[k] not in entry.KEYS[k]:
            raise ValueError(f"traffic key {k!r} takes one of {list(entry.KEYS[k])}, not {traffic[k]!r}")


def setup(cfg: dict, traffic: dict, seed: int, program=system_under_test, root=registry.HERE) -> State:
    entry = registry.entry(traffic["entry"], root)
    validate(traffic, entry)
    if cfg["input"] not in INPUTS:
        raise ValueError(f"unknown input residency {cfg['input']!r}; one of {INPUTS}")
    gen = registry.generator(cfg["field"]["generator"], root)
    st = State(cfg, traffic, seed, program, program.compressor(cfg), entry, gen, gen.base(cfg["field"]))
    entry.setup(st)
    return st


def run_window(st: State, seconds: float) -> Window:
    w = Window([])
    t_end = time.perf_counter() + seconds
    while not w.calls or time.perf_counter() < t_end:
        try:
            w.calls.append(st.entry.call(st, len(w.calls)))
        except Exception as e:  # a call that raises is an answer that never came
            w.failed += 1
            w.error = f"{type(e).__name__}: {e}"
            break
    return w


def end_to_end(st: State, w: Window) -> dict:
    """Every end-to-end value this run can give, by metric name."""
    return st.entry.end_to_end(st, w) if w.calls else {}


def check(st: State, w: Window) -> dict:
    """The numbers compared, each ``{"value": v, "limit": l}``. An answer
    that does not decode says the wrong thing: it reads ``inf``. The CPU
    witness decodes in a thread beside the chip's decodes."""
    slack = float(st.cfg["guarantees"]["bound_slack"])
    worst, diff = float("nan"), -1
    if w.calls:
        worst, diff = 0.0, 0
        buf, idx = st.entry.witness(st, w)
        idx, kept, x = set(idx), {}, (None, None, None)  # x: (snapshot, host array, bound)
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            witness = pool.submit(st.program.decode_on_cpu, st.cfg, buf)
            for j, (k, y) in enumerate(st.entry.answers(st, w)):
                if x[0] != k:
                    xk = st.host_snapshot(k)
                    x = (k, xk, reference.bound_abs(float(xk.min()), float(xk.max()), st.cfg))
                worst = max(worst, float("inf") if y is None else reference.err_over_bound(x[1], y, x[2]))
                if j in idx:
                    kept[j] = np.zeros(0, np.float32) if y is None else y
            try:
                y_cpu = witness.result()
            except Exception as e:
                print(f"bench: the CPU does not decode the container: {type(e).__name__}: {e}", file=sys.stderr)
                y_cpu = np.zeros(0, np.float32)
        diff = sum(reference.diff_points(y, y_cpu) for y in kept.values())
    return {
        "max_err_over_bound": {"value": worst, "limit": 1.0 + slack},
        "cpu_decode_diff_points": {"value": diff, "limit": 0},
    }


def correct(checks: dict, w: Window) -> bool:
    ok = (v["value"] <= v["limit"] and v["value"] >= 0 for v in checks.values())
    return bool(w.calls) and w.failed == 0 and all(ok)
