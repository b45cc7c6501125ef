"""Lossless encode's self time in the compress window, as a share of the window (probe ``encode``)."""
from bench.readers import probe_share

PROBES = ("encode",)


def read(run):
    return probe_share(run, "encode")
