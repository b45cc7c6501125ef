"""The share of the traced window in which no operation ran on the chip."""
from bench.readers import device_idle

PROBES = ()


def read(run):
    return device_idle(run)
