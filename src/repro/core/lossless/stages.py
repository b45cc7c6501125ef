"""Lossless stage registry — the extension point of the encoding layer.

A *stage* is one lossless transform in a pipeline (Huffman, run-reduction,
bit-plane shuffle, ...). Each stage is self-describing:

* ``encode(data) -> (payload, header)`` / ``decode(payload, header)`` —
  the transform itself over a uint8 stream; ``header`` is a small dict of
  scalars the decoder needs.
* ``pack_header`` / ``unpack_header`` — a compact binary serialization of
  that dict, embedded in the pipeline stream (repro.core.lossless.pipelines)
  so stage metadata costs a handful of bytes, not JSON. Stages that don't
  provide packers fall back to JSON bytes.
* ``estimate(stats) -> float`` — a cheap cost hook: predicted output bytes
  per input byte given sampled stream statistics (see
  repro.core.lossless.orchestrate.stream_stats). The orchestrator uses
  these to rank candidate pipelines before trial-encoding.
* ``encode_device(data) -> (payload, header)`` — optional device twin of
  ``encode`` taking a ``jax.Array`` uint8 stream and returning a *device*
  uint8 payload, byte-identical to ``encode``'s (the engine contract, see
  repro.core.lossless.engine). Stages without one fall back to the numpy
  path when a pipeline runs device-resident. A twin that also takes
  ``limit`` (a function of its header: the payload size at which the
  pipeline stores the stage through) may return ``None`` for a payload
  that reaches it, without building it.
* ``decode_device(payload, header) -> jax.Array`` — optional device twin
  of ``decode`` under the same bit-identity contract, accepting host
  bytes-like or device uint8 payloads and returning a *device* uint8
  stream. Stages without one pull the stream to host when a pipeline
  decodes device-resident.

Third-party stages register with :func:`register_stage` and are immediately
usable in :func:`repro.core.lossless.pipelines.register_pipeline` — core
never needs to know their names. Name collisions raise at registration
(pass ``overwrite=True`` to replace deliberately).
"""
from __future__ import annotations

import dataclasses
import json
import struct
from typing import Callable

import numpy as np

from . import bitshuffle as _bit
from . import huffman as _hf
from . import rre as _rre
from . import tcms as _tcms


@dataclasses.dataclass(frozen=True)
class Stage:
    name: str
    encode: Callable[[np.ndarray], tuple]
    decode: Callable[[bytes, dict], np.ndarray]
    estimate: Callable[[dict], float]
    pack_header: Callable[[dict], bytes]
    unpack_header: Callable[[bytes], dict]
    # portable: decoding never needs an optional dependency. Durable artifacts
    # (checkpoints, relayed gradients) restrict auto-selection to portable
    # pipelines so they stay restorable on any machine.
    portable: bool = True
    # device twins (bit-identity contract); None = host-only direction
    encode_device: Callable | None = None
    decode_device: Callable | None = None


_REGISTRY: dict[str, Stage] = {}


def _json_pack(hdr: dict) -> bytes:
    return json.dumps(hdr).encode()


def _json_unpack(raw: bytes) -> dict:
    return json.loads(raw.decode())


def register_stage(
    name: str,
    encode: Callable,
    decode: Callable,
    *,
    estimate: Callable[[dict], float] | None = None,
    pack_header: Callable[[dict], bytes] | None = None,
    unpack_header: Callable[[bytes], dict] | None = None,
    portable: bool = True,
    encode_device: Callable | None = None,
    decode_device: Callable | None = None,
    overwrite: bool = False,
) -> Stage:
    """Register a lossless stage under ``name``.

    Raises ``ValueError`` on collision unless ``overwrite=True``, listing
    the registered names so typos fail loudly at registration time.
    """
    if name in _REGISTRY and not overwrite:
        raise ValueError(
            f"stage {name!r} is already registered "
            f"(registered stages: {', '.join(sorted(_REGISTRY))}); "
            "pass overwrite=True to replace it"
        )
    stage = Stage(
        name=name,
        encode=encode,
        decode=decode,
        estimate=estimate or (lambda stats: 1.0),
        pack_header=pack_header or _json_pack,
        unpack_header=unpack_header or _json_unpack,
        portable=portable,
        encode_device=encode_device,
        decode_device=decode_device,
    )
    _REGISTRY[name] = stage
    return stage


def get_stage(name: str) -> Stage:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown lossless stage {name!r}; "
            f"registered stages: {', '.join(sorted(_REGISTRY))}"
        ) from None


def registered_stages() -> dict[str, Stage]:
    return dict(_REGISTRY)


# ------------------------------------------------------------ built-in stages
# Binary header packers: each built-in stage's decode metadata is a few
# fixed-width integers, so headers pack to <= 17 bytes.

def _pack_hf(h):
    # versioned: the bare 8-byte form predates the per-chunk byte-offset
    # table ("offs", see huffman.offset_table) and still decodes — streams
    # without it just lose the device decoder's parallel chunk entry points.
    offs = h.get("offs")
    if offs is None:
        return struct.pack("<Q", h["n"])
    return struct.pack("<QB", h["n"], 1) + offs


def _unpack_hf(raw):
    if len(raw) == 8:
        return {"n": struct.unpack_from("<Q", raw)[0]}
    n, ver = struct.unpack_from("<QB", raw)
    out = {"n": n}
    if ver == 1:
        out["offs"] = bytes(raw[9:])
    return out


def _pack_rre(h):
    return struct.pack("<QQB", h["n"], h["nsym"], h["k"])


def _unpack_rre(raw):
    n, nsym, k = struct.unpack_from("<QQB", raw)
    return {"n": n, "nsym": nsym, "k": k}


def _pack_tcms(h):
    return struct.pack("<QB", h["n"], h["k"])


def _unpack_tcms(raw):
    n, k = struct.unpack_from("<QB", raw)
    return {"n": n, "k": k}


def _pack_bit(h):
    return struct.pack("<QI", h["n"], h["block"])


def _unpack_bit(raw):
    n, block = struct.unpack_from("<QI", raw)
    return {"n": n, "block": block}


def _pack_zstd(h):
    return struct.pack("<B", 1 if h.get("c", "zstd") == "zlib" else 0)


def _unpack_zstd(raw):
    return {"c": "zlib" if raw[0] else "zstd"}


# Cost hooks: predicted output fraction (bytes out per byte in) from the
# sampled stats dict {n, entropy, zero_frac, run_frac, outlier_frac}. These
# are deliberately crude — they ignore how earlier stages reshape the stream
# — because the orchestrator refines the ranking with a trial encode; their
# job is a cheap, monotone-ish pre-score.

def _est_hf(s):
    n = max(int(s.get("n", 1)), 1)
    # 256B lens + per chunk: 2B payload size + 4B header byte-offset entry
    table = (256.0 + 6.0 * (n // _hf.CHUNK + 1)) / n
    return min(1.0, s["entropy"] / 8.0 + table)


def _est_rre(k):
    def est(s):
        kept = 1.0 - float(s["run_frac"]) ** k
        return min(1.0, kept + 1.0 / (8.0 * k))

    return est


def _est_rze(k):
    def est(s):
        kept = 1.0 - float(s["zero_frac"]) ** k
        return min(1.0, kept + 1.0 / (8.0 * k))

    return est


def _est_unit(s):
    return 1.0  # bijective reshuffles (tcms, bit1) pay off downstream


def _est_zstd(s):
    return max(0.02, 0.85 * s["entropy"] / 8.0)


def _zstd_encode(data: np.ndarray):
    # zstandard is an optional dependency: fall back to stdlib zlib and
    # record the codec actually used so decode dispatches correctly
    try:
        import zstandard

        return zstandard.ZstdCompressor(level=6).compress(data.tobytes()), {"c": "zstd"}
    except ImportError:
        import zlib

        return zlib.compress(data.tobytes(), 6), {"c": "zlib"}


def _zstd_decode(payload: bytes, header: dict) -> np.ndarray:
    if header.get("c", "zstd") == "zlib":
        import zlib

        return np.frombuffer(zlib.decompress(payload), np.uint8)
    try:
        import zstandard
    except ImportError as e:
        raise ImportError(
            "this stream was compressed with the optional 'zstandard' package; install it to decode"
        ) from e
    return np.frombuffer(zstandard.ZstdDecompressor().decompress(payload), np.uint8)


# Device twins resolve the engine lazily: repro.core.lossless.engine pulls
# in jax, which host-only consumers of this module never need.

def _dev(fn_name: str, sized: bool = False, **fixed):
    # only a sized twin (rre/rze) acts on the store-through limit
    def call(data, limit=None, _fn=fn_name, _fixed=fixed):
        from . import engine

        kw = dict(_fixed, limit=limit) if sized else _fixed
        return getattr(engine, _fn)(data, **kw)

    return call


def _devd(fn_name: str):
    # decode twins take a uniform (payload, header) signature — any stage
    # parameter (k, block) already rides in the header
    def call(payload, header, _fn=fn_name):
        from . import engine

        return getattr(engine, _fn)(payload, header)

    return call


def _register_builtins() -> None:
    register_stage("hf", _hf.encode, _hf.decode, estimate=_est_hf,
                   pack_header=_pack_hf, unpack_header=_unpack_hf,
                   encode_device=_dev("hf_encode_device"),
                   decode_device=_devd("hf_decode_device"))
    register_stage("bit1", _bit.bitshuffle_encode, _bit.bitshuffle_decode,
                   estimate=_est_unit, pack_header=_pack_bit, unpack_header=_unpack_bit,
                   encode_device=_dev("bit1_encode_device"),
                   decode_device=_devd("bit1_decode_device"))
    # not portable: when zstandard is installed at encode time, decoding the
    # stream needs it too (the zlib fallback only engages when it's absent);
    # also host-only — no device twins
    register_stage("zstd", _zstd_encode, _zstd_decode, estimate=_est_zstd,
                   pack_header=_pack_zstd, unpack_header=_unpack_zstd, portable=False)
    for k in (1, 2, 4, 8):
        register_stage(f"rre{k}", (lambda d, k=k: _rre.rre_encode(d, k)), _rre.rre_decode,
                       estimate=_est_rre(k), pack_header=_pack_rre, unpack_header=_unpack_rre,
                       encode_device=_dev("rre_encode_device", sized=True, k=k),
                       decode_device=_devd("rre_decode_device"))
        register_stage(f"rze{k}", (lambda d, k=k: _rre.rze_encode(d, k)), _rre.rze_decode,
                       estimate=_est_rze(k), pack_header=_pack_rre, unpack_header=_unpack_rre,
                       encode_device=_dev("rze_encode_device", sized=True, k=k),
                       decode_device=_devd("rze_decode_device"))
        register_stage(f"tcms{k}", (lambda d, k=k: _tcms.tcms_encode(d, k)), _tcms.tcms_decode,
                       estimate=_est_unit, pack_header=_pack_tcms, unpack_header=_unpack_tcms,
                       encode_device=_dev("tcms_encode_device", k=k),
                       decode_device=_devd("tcms_decode_device"))


_register_builtins()
