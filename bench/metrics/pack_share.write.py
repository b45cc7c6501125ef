"""Block scatter, level reorder and container packing's self time in the
compress window, as a share of the window: the program's spans
``compress.scatter``, ``compress.reorder`` and ``compress.pack``."""
from bench.program_calls import self_share

PROBES = ()


def read(run):
    return self_share(run, "compress", ("compress.scatter", "compress.reorder", "compress.pack"))
