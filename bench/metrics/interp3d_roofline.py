"""The Pallas interp3d kernel's share of its HBM roofline: the bytes it
must move at 819 GB/s over its device time in the trace. HBM only: the
v5e's f32 vector peak is not published, so no compute roof is used."""
from bench.kernels import blocks_of, interp3d_bytes
from bench.readers import hbm_roofline

PROBES = ()
KERNEL = "interp3d"


def read(run):
    return hbm_roofline(run, KERNEL, lambda r: interp3d_bytes(blocks_of(r.cfg["field"]["shape"])))
