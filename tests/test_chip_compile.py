"""Compile the main path's kernels and predictor passes for one TPU v5e,
at the paper's size, without a chip (XLA:TPU compiling for a described
topology). What the chip's compiler refuses — a block shape off the
(8,128) tiling, a vector layout Mosaic cannot lower, more VMEM or HBM than
the chip has — fails here at no chip time. Nothing runs, so nothing here
says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.predictor import compress_blocks, decompress_blocks, default_steps

NB = 32768                 # 17^3 blocks of a 512^3 field (Nyx at its published size)
STREAM = 128 << 20         # bytes: a 128 MiB lossless-stage stream
HBM = int(15.75 * 2**30)   # what XLA:TPU lets one v5e program hold


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # a persistent-cache entry compiled here could not be read back
    # without a chip; keep these compiles out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert used <= HBM, f"{used / 2**30:.2f} GiB > {HBM / 2**30:.2f} GiB"
    return used


def test_interp3d_compiles_at_512cubed(one_chip):
    from repro.kernels.interp3d.interp3d import interp3d_compress

    steps = default_steps(3)
    f = jax.jit(lambda b, t, i: interp3d_compress(b, t, i, steps, 16, False))
    c = f.lower(_spec(one_chip, (17, 17, 17, NB), jnp.float32),
                _spec(one_chip, (), jnp.float32), _spec(one_chip, (), jnp.float32)).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.mark.parametrize("direction", ["shuffle", "unshuffle"])
def test_bitshuffle_compiles_on_8192_byte_blocks(one_chip, direction):
    from repro.kernels.bitshuffle.bitshuffle import bitshuffle_pallas_raw, bitunshuffle_pallas_raw

    fn = bitshuffle_pallas_raw if direction == "shuffle" else bitunshuffle_pallas_raw
    c = jax.jit(lambda x: fn(x, False)).lower(
        _spec(one_chip, (STREAM // 8192, 8192), jnp.uint8)).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_histogram256_compiles(one_chip):
    from repro.kernels.histogram.histogram import histogram256_raw

    c = jax.jit(lambda x: histogram256_raw(x, False)).lower(
        _spec(one_chip, (STREAM,), jnp.uint8)).compile()
    assert "tpu_custom_call" in c.as_text()


def _no_matmul(compiled) -> None:
    """The predictor is matmul-free: no MXU pass, so no precision choice
    that could make a TPU-written archive decode differently on a CPU."""
    assert not re.search(r"\b(dot|convolution)\(", compiled.as_text())


def test_compress_blocks_fits_one_v5e(one_chip):
    steps = default_steps(3)
    c = jax.jit(lambda b, t, i: compress_blocks(b, t, i, steps, 16)).lower(
        _spec(one_chip, (NB, 17, 17, 17), jnp.float32), _spec(one_chip, (), jnp.float32),
        _spec(one_chip, (), jnp.float32)).compile()
    _fits(c)
    _no_matmul(c)


def test_decompress_blocks_fits_one_v5e(one_chip):
    steps = default_steps(3)
    blk = lambda dt: _spec(one_chip, (NB, 17, 17, 17), dt)
    c = jax.jit(lambda q, a, v, t: decompress_blocks(q, a, v, t, steps, 16)).lower(
        blk(jnp.uint8), blk(jnp.float32), blk(jnp.float32), _spec(one_chip, (), jnp.float32)).compile()
    _fits(c)
    _no_matmul(c)
