"""Host prep's self time in the compress window, as a share of the window:
the program's spans ``compress.ingest`` (the input's copy to host, the
finite scan, the bound) and ``compress.prep`` (pad and block gather)."""
from bench.program_calls import self_share

PROBES = ()


def read(run):
    return self_share(run, "compress", ("compress.ingest", "compress.prep"))
