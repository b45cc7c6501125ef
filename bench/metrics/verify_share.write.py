"""Verify's self time in the compress window, as a share of the window (probe ``verify``)."""
from bench.readers import probe_share

PROBES = ("verify",)


def read(run):
    return probe_share(run, "verify")
