"""Host spans around calls into the program's layers.

A probe file (``probes/<name>.json``) names one callable of the program,
``"module:attr.path"``. Installing it swaps that attribute for a wrapper
that records a span (start, end, parent) on the host clock, waits for any
device arrays the call returns, and marks the same interval as a
``jax.profiler.TraceAnnotation`` named ``bench:<probe>`` so that it lands on
the device trace's clock. Probes are installed only for a traced run and
removed after its window.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import time

import jax

PREFIX = "bench:"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    parent: int = -1  # index into the recorder's spans, -1 for none

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Recorder:
    """Spans of one thread, in the order they opened."""

    def __init__(self):
        self.spans: list[Span] = []
        self._open: list[int] = []
        self._undo: list[tuple] = []

    def span(self, name: str, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            i = len(self.spans)
            self.spans.append(Span(name, time.perf_counter(), parent=self._open[-1] if self._open else -1))
            self._open.append(i)
            try:
                with jax.profiler.TraceAnnotation(PREFIX + name):
                    out = fn(*args, **kwargs)
                    for leaf in jax.tree_util.tree_leaves(out):
                        if isinstance(leaf, jax.Array):
                            leaf.block_until_ready()
                return out
            finally:
                self._open.pop()
                self.spans[i].end = time.perf_counter()

        return wrapper

    def install(self, name: str, target: str):
        """Wrap ``target`` (``"pkg.module:Attr.path"``) under probe ``name``."""
        modname, _, attrs = target.partition(":")
        owner = importlib.import_module(modname)
        *path, last = attrs.split(".")
        for a in path:
            owner = getattr(owner, a)
        original = owner.__dict__[last] if isinstance(owner, type) else getattr(owner, last)
        setattr(owner, last, self.span(name, original))
        self._undo.append((owner, last, original))

    def uninstall(self):
        while self._undo:
            owner, last, original = self._undo.pop()
            setattr(owner, last, original)

    def self_seconds(self) -> dict[str, float]:
        """Each probe's self time: its spans' durations less the parts
        that probe spans nested directly inside them cover."""
        out: dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.seconds
            if s.parent >= 0:
                p = self.spans[s.parent].name
                out[p] = out.get(p, 0.0) - s.seconds
        return out
