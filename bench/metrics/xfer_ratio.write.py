"""Bytes that crossed between host and device in the window's compress
calls (the program's ``h2d_bytes`` and ``d2h_bytes`` counters) per byte
of input (``in_bytes``)."""
from bench.program_calls import counter, window_calls

PROBES = ()


def read(run):
    recs = window_calls(run, "compress")
    n_in = counter(recs, "in_bytes")
    if not recs or n_in <= 0:
        return None
    return (counter(recs, "h2d_bytes") + counter(recs, "d2h_bytes")) / n_in
