"""One caller compresses distinct snapshots back to back.

Set-up compresses snapshot 0, a cold call that compiles or loads the
programs the window's calls run. The window's call ``i`` compresses
snapshot ``i + 1``, which no call has seen: new content, as each call of
an archive writer or an in-situ simulation brings. A program whose shapes
follow the data compiles inside the call that needs it, as it does for
that user. Each call is ended by its returned bytes.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.harness import Call

KEYS: dict = {}


def setup(st):
    st.comp.compress(st.snapshot(0))


def call(st, i: int) -> Call:
    k = i + 1
    x = st.snapshot(k)
    t0 = time.perf_counter()
    buf = st.comp.compress(x)
    dt = time.perf_counter() - t0
    return Call(int(x.nbytes), len(buf), dt, buf, k)


def answers(st, w):
    dec = st.program.compressor(st.cfg)
    for j, c in enumerate(w.calls):
        try:
            y = dec.decompress(c.answer)
        except Exception as e:
            print(f"bench: answer {j} does not decode: {type(e).__name__}: {e}", file=sys.stderr)
            y = None
        yield c.source, y


def witness(st, w):
    """One container of the window, drawn from the seed."""
    j = int(np.random.default_rng(st.seed % 2**64).integers(len(w.calls)))
    return w.calls[j].answer, [j]


def end_to_end(st, w) -> dict:
    n_in = sum(c.nbytes_in for c in w.calls)
    return {"compress_MBps": n_in / w.seconds / 1e6, "cr": n_in / sum(c.nbytes_out for c in w.calls)}
