"""The system under test as the harness drives it: the compressor that a
configuration's ``spec`` describes, and the host CPU's decode of a
container, the witness of the guarantee that a container written on the
chip decodes to the same values anywhere. The control
(``bench/control.py``) stands in its place with the same two functions.
"""
from __future__ import annotations


def compressor(cfg: dict):
    from repro.core import Compressor, CompressorSpec

    return Compressor(CompressorSpec(**cfg["spec"]))


def decode_on_cpu(cfg: dict, buf: bytes):
    """The same container decoded by the host engine on the host's CPU
    device."""
    import jax

    from repro.core import Compressor, CompressorSpec

    with jax.default_device(jax.devices("cpu")[0]):
        return Compressor(CompressorSpec(**dict(cfg["spec"], engine="numpy"))).decompress(buf)
