"""Tuning's self time in the compress window, as a share of the window (probe ``tune``)."""
from bench.readers import probe_share

PROBES = ("tune",)


def read(run):
    return probe_share(run, "tune")
