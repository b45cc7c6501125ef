"""XLA compile seconds inside the window's compress calls (the program's
``compile_s`` counter), as a share of the window."""
from bench.program_calls import counter, window_calls

PROBES = ()


def read(run):
    recs = window_calls(run, "compress")
    if not recs or run.window_s <= 0:
        return None
    return 100.0 * counter(recs, "compile_s") / run.window_s
