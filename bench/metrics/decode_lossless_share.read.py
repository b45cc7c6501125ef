"""The lossless decode's self time in the decompress window, as a share of
the window (probe ``decode_lossless``)."""
from bench.readers import probe_share

PROBES = ("decode_lossless",)


def read(run):
    return probe_share(run, "decode_lossless")
