"""The program's own records of the calls in a run's window.

The compressor keeps a record of every top-level ``compress`` and
``decompress`` call (``repro.core.spans``): its spans on the host clock
and its counters. The per-layer metrics that read them take the window's
records from here: the records of the cell's call whose start is no
earlier than the last one's end less the window's wall time and one
second. Set-up's warm call starts well before that, and the metrics are
read before the check makes its decodes. A program without
``repro.core.spans`` gives no records, and every reader then returns
``None``.
"""
from __future__ import annotations

SLACK_S = 1.0


def select(records, call: str, window_s: float) -> list:
    """The records of ``call`` that fall in a window of ``window_s``
    seconds ending with the last of them."""
    mine = [r for r in records if r.name == call]
    if not mine:
        return []
    lo = max(r.t1 for r in mine) - window_s - SLACK_S
    return [r for r in mine if r.t0 >= lo]


def window_calls(run, call: str) -> list:
    try:
        from repro.core import spans
    except ImportError:
        return []
    return select(spans.calls(), call, run.window_s)


def self_share(run, call: str, names) -> float | None:
    """The self seconds of the spans ``names`` in the window's ``call``
    records, as a share of the window, in %."""
    recs = window_calls(run, call)
    if not recs or run.window_s <= 0:
        return None
    return 100.0 * sum(r.self_seconds().get(n, 0.0) for r in recs for n in names) / run.window_s


def counter(recs, key: str) -> float:
    return sum(r.counters.get(key, 0) for r in recs)
