"""One reader decodes, back to back, the container that set-up wrote with
the program's own compress of snapshot 0.

``out`` is where the reader wants the array: ``"numpy"`` (a host array,
each call ended by it) or ``"device"`` (a ``jax.Array``, each call ended
when it is ready).
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import Call

KEYS = {"out": ("numpy", "device")}


def setup(st):
    st.container = st.comp.compress(st.snapshot(0))
    _decode(st)


def _decode(st):
    y = st.comp.decompress(st.container, out=st.traffic["out"])
    return y.block_until_ready() if st.traffic["out"] == "device" else y


def call(st, i: int) -> Call:
    t0 = time.perf_counter()
    y = _decode(st)
    dt = time.perf_counter() - t0
    return Call(len(st.container), int(y.nbytes), dt, y, 0)


def answers(st, w):
    for c in w.calls:
        yield 0, np.asarray(c.answer)


def witness(st, w):
    """The container every call decoded, against each call's answer."""
    return st.container, range(len(w.calls))


def end_to_end(st, w) -> dict:
    return {"decompress_MBps": sum(c.nbytes_out for c in w.calls) / w.seconds / 1e6}
