"""Chip benchmark of the compressor, driven by the files beside this one.

Run one cell of ``BENCHMARK.json`` from the root of a checkout::

    python3 -m bench.run --workload nyx512-cr.write --seed 7 --seconds 30 --trace 0

A cell names a configuration (``configs/<name>.json``: field generator
and shape, bound, ``CompressorSpec`` fields, where the input lives) and a
traffic mix (``traffic/<name>.json``: data only, naming the entry driver
the window drives and the keys that driver takes). Field generators are
``generators/<name>.py``, entry drivers ``entries/<name>.py``, per-layer
metric readers ``metrics/<name>.py``, and the host spans the readers use
come from the probes in ``probes/<name>.json``. Everything is found by
name, so a new cell, configuration, generator, mix, entry, metric or
probe is a new file and no edit.
"""
