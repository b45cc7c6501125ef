"""Predictor's self time in the compress window, as a share of the window (probe ``predict``)."""
from bench.readers import probe_share

PROBES = ("predict",)


def read(run):
    return probe_share(run, "predict")
