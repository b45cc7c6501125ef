"""Host spans and counters of the compressor's compress and decompress calls.

``call(name)`` opens the record of one top-level ``compress`` or
``decompress`` on the calling thread; ``span(name)`` marks a stretch of
host work inside it. Each span is also a ``jax.profiler.TraceAnnotation``
named ``repro.<name>``, so in a profile it lands on the device trace's
clock and an idle stretch of the chip can be put down to what the host
was doing. A span records host time (``time.perf_counter``) only: it never
waits for a device array, so device work shows in the span that waits for
its result, and in the device trace.

A ``call`` opened while one is already open on the thread (verify's
decode inside a compress, the repair ladder's inner compressor) becomes a
span of the open record, not a record of its own. When the top-level call
closes, its :class:`Record` goes to a bounded process-wide log
(:func:`calls`); the compressor also puts it at
``last_telemetry["trace"]``.

Counters live on the open record (:func:`count`):

- ``in_bytes``: the bytes the caller handed in (the f32 field, or the
  container);
- ``h2d_bytes`` / ``d2h_bytes``: array bytes that crossed between host and
  device, counted by :func:`to_device` and :func:`to_host`, the one pair of
  helpers the call's paths cross through;
- ``compiles`` / ``compile_s``: XLA backend compiles on the thread while
  the record is open, from a ``jax.monitoring`` listener; each compile's
  seconds also go to the innermost open span's ``compile_s``.

Recording is always on. Outside a profiler session a ``TraceAnnotation``
does nothing, so a span costs two clock reads and a list append.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "repro."
LOG_SIZE = 256  # records the process keeps, newest last
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COUNTERS = ("in_bytes", "h2d_bytes", "d2h_bytes", "compiles", "compile_s")


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float = 0.0
    parent: int = -1  # index of the enclosing span in Record.spans; -1: the call itself
    compile_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Record:
    """One top-level call: its spans in the order they opened, and its
    counters."""

    name: str
    t0: float
    t1: float = 0.0
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=lambda: dict.fromkeys(COUNTERS, 0))

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def self_seconds(self) -> dict[str, float]:
        """Seconds by span name, each span less the spans nested directly
        inside it; the call's own name holds what no span covers."""
        out = {self.name: self.seconds}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.seconds
            parent = self.name if s.parent < 0 else self.spans[s.parent].name
            out[parent] -= s.seconds
        return out


class _Open(threading.local):
    def __init__(self):
        self.record: Record | None = None
        self.stack: list[int] = []  # indices of the open spans, innermost last


_open = _Open()
_log: collections.deque = collections.deque(maxlen=LOG_SIZE)
_log_lock = threading.Lock()


@contextlib.contextmanager
def span(name: str):
    """Mark the body as span ``name`` of the thread's open call (a bare
    profiler annotation where none is open)."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        rec = _open.record
        if rec is None:
            yield None
            return
        stack = _open.stack
        s = Span(name, time.perf_counter(), parent=stack[-1] if stack else -1)
        rec.spans.append(s)
        stack.append(len(rec.spans) - 1)
        try:
            yield s
        finally:
            stack.pop()
            s.t1 = time.perf_counter()


@contextlib.contextmanager
def call(name: str):
    """Open the thread's record of a top-level call and yield it; inside an
    open call, a span of that call (yielded as its :class:`Span`)."""
    if _open.record is not None:
        with span(name) as s:
            yield s
        return
    rec = Record(name, time.perf_counter())
    _open.record, _open.stack = rec, []
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield rec
    finally:
        rec.t1 = time.perf_counter()
        _open.record = None
        with _log_lock:
            _log.append(rec)


def count(key: str, n) -> None:
    """Add ``n`` to counter ``key`` of the thread's open call, if any."""
    rec = _open.record
    if rec is not None:
        rec.counters[key] = rec.counters.get(key, 0) + n


def calls() -> list[Record]:
    """The process's last :data:`LOG_SIZE` top-level records, oldest first."""
    with _log_lock:
        return list(_log)


def to_device(a, dtype=None):
    """``jnp.asarray(a, dtype)``, counting a host array's bytes as they
    cross to the device."""
    out = jnp.asarray(a, dtype)
    if not isinstance(a, jax.Array):
        count("h2d_bytes", int(out.nbytes))
    return out


def to_host(a, dtype=None) -> np.ndarray:
    """``np.asarray(a, dtype)``, counting a device array's bytes as they
    cross to the host."""
    if isinstance(a, jax.Array):
        count("d2h_bytes", int(a.nbytes))
    return np.asarray(a, dtype)


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    rec = _open.record
    if rec is None:
        return
    rec.counters["compiles"] += 1
    rec.counters["compile_s"] += seconds
    if _open.stack:
        rec.spans[_open.stack[-1]].compile_s += seconds


jax.monitoring.register_event_duration_secs_listener(_on_duration)
