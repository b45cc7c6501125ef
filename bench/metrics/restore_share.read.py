"""The host decode's restore and scatter self time in the decompress
window, as a share of the window: the program's spans
``decompress.restore`` (code grid, anchors, outliers, block gathers) and
``decompress.scatter`` (block scatter and crop)."""
from bench.program_calls import self_share

PROBES = ()


def read(run):
    return self_share(run, "decompress", ("decompress.restore", "decompress.scatter"))
