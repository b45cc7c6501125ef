"""The control of the check: the plain reference codec put in the
program's place. In bfloat16, the nearest precision below the
configurations' float32, its answers must come out as not correct; in
float32 they keep the bound and come out correct.

    python3 -m bench.control --workload nyx512-cr.write --seeds 11,12,13

runs, for each seed, the cell's set-up, a window of one call and the
check, as ``bench.run`` does, with ``PlainProgram`` as the program, once
in each precision, at the cell's own size on the chip, and prints each
verdict with the numbers compared. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import io
import json
import sys

import numpy as np

from . import harness, reference, registry


class PlainCodec:
    """``compress`` writes the plain codec's decoded values, computed on
    the device in ``dtype`` at the bound the reference works out from the
    input; ``decompress`` reads them back."""

    def __init__(self, cfg: dict, dtype):
        self.cfg, self.dtype = cfg, dtype

    def compress(self, x) -> bytes:
        import jax.numpy as jnp

        xd = jnp.asarray(x)
        eb = reference.bound_abs(float(jnp.min(xd)), float(jnp.max(xd)), self.cfg)
        out = io.BytesIO()
        np.save(out, np.asarray(reference.plain_codec(xd, eb, self.dtype)))
        return out.getvalue()

    def decompress(self, buf: bytes, out: str = "numpy"):
        y = np.load(io.BytesIO(buf))
        if out == "device":
            import jax.numpy as jnp

            return jnp.asarray(y)
        return y


class PlainProgram:
    """The plain codec with the program's two functions: ``compressor(cfg)``
    and ``decode_on_cpu(cfg, buf)``."""

    def __init__(self, dtype):
        self.dtype = dtype

    def compressor(self, cfg: dict) -> PlainCodec:
        return PlainCodec(cfg, self.dtype)

    def decode_on_cpu(self, cfg: dict, buf: bytes):
        return PlainCodec(cfg, self.dtype).decompress(buf)


def verdict(workload: str, seed: int, dtype, cfg: dict | None = None) -> dict:
    """The harness's verdict on one call of ``workload`` with the plain
    codec in ``dtype`` as the program; ``cfg`` overrides the cell's
    configuration (the tests shrink it)."""
    cell = registry.cell(registry.benchmark(), workload)
    cfg = cfg or registry.config(cell["config"])
    st = harness.setup(cfg, registry.traffic(cell["traffic"]), seed, program=PlainProgram(dtype))
    w = harness.run_window(st, 0.0)
    checks = harness.check(st, w)
    return {"correct": harness.correct(checks, w), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("bench.control: no TPU; nothing run", file=sys.stderr)
        return 2
    for s in args.seeds.split(","):
        for name, dt in (("reference_f32", jnp.float32), ("control_bf16", jnp.bfloat16)):
            v = verdict(args.workload, int(s), dt)
            print(json.dumps({"workload": args.workload, "seed": int(s), "program": name, **v}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
