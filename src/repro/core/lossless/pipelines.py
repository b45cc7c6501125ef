"""Composable lossless pipelines over the stage registry (paper §5.2, Fig. 7).

A pipeline is a named sequence of registered stages
(:mod:`repro.core.lossless.stages`); :func:`register_pipeline` validates
every stage name against the registry at registration time, so a typo fails
with the list of known stages instead of deep inside an encode. The two
cuSZ-Hi pipelines:

    CR mode:  hf  -> rre4 -> tcms8 -> rze1      (ratio-preferred)
    TP mode:  tcms1 -> bit1 -> rre1             (throughput-preferred)

``pipeline="auto"`` (see :mod:`repro.core.lossless.orchestrate`) samples the
stream and picks the best-fit registered pipeline per field.

Device fast path: when ``encode`` receives a ``jax.Array``, each stage with
an ``encode_device`` twin (repro.core.lossless.engine) runs jit-compiled on
the device and the stream chains between stages as a device array — the
bytes only land on host once, in the final packed stream. The engine's
bit-identity contract makes the result byte-equal to the numpy path, so
the choice of path is invisible to decoders and golden fixtures. A twin
that sizes its payload first (rre/rze) is handed the store-through limit
and, where its payload would reach it, skips building it: the record is
the same store-through either way. A stage
without a device twin (e.g. ``zstd``) drops the stream to host and the
remaining stages run the numpy path. ``decode(buf, device=True)`` is the
symmetric read path: stages with ``decode_device`` twins chain the stream
device-resident back to a device uint8 array, same bytes as the host
decode.

Stream format (v2, this module's framing): ``b"LLP2"`` magic, then one
record per stage — flags byte (bit0 = store-through skip for stages that
expanded the stream), name, and the stage's *binary-packed* header — then
the final payload. Streams written before this format (a JSON meta block
prefixed by its u32 length) are detected by the missing magic and decoded
through the same stage registry, so old containers keep working.
"""
from __future__ import annotations

import inspect
import json
import struct

import numpy as np

from ..spans import count, span, to_host
from .stages import get_stage

_MAGIC = b"LLP2"

PIPELINES: dict[str, tuple] = {}  # name -> stage-name tuple (live registry)


def register_pipeline(name: str, stages, *, overwrite: bool = False) -> tuple:
    """Register a named pipeline; every stage must already be registered."""
    stages = tuple(stages)
    for s in stages:
        get_stage(s)  # raises with the registered-stage list on typos
    if name in PIPELINES and not overwrite and PIPELINES[name] != stages:
        raise ValueError(
            f"pipeline {name!r} is already registered as {PIPELINES[name]}; "
            "pass overwrite=True to replace it"
        )
    PIPELINES[name] = stages
    return stages


def get_pipeline(name: str) -> tuple:
    try:
        return PIPELINES[name]
    except KeyError:
        raise ValueError(
            f"unknown pipeline {name!r}; "
            f"registered pipelines: {', '.join(sorted(PIPELINES))} (or 'auto')"
        ) from None


def registered_pipelines() -> dict[str, tuple]:
    return dict(PIPELINES)


register_pipeline("cr", ("hf", "rre4", "tcms8", "rze1"))
register_pipeline("tp", ("tcms1", "bit1", "rre1"))
register_pipeline("hf", ("hf",))
register_pipeline("none", ())
# baseline pipelines (see repro.core.baselines)
register_pipeline("fz", ("bit1", "rre1"))
# beyond-paper: CR pipeline with an open-source zstd tail (replaces the
# role Bitcomp plays for cuSZ-IB, without the proprietary dependency)
register_pipeline("crz", ("hf", "rre4", "tcms8", "rze1", "zstd"))
# bit1-first variant: bit-plane shuffle up front so the run-reduction sees
# plane-major redundancy, Huffman mops up the survivors
register_pipeline("fzh", ("bit1", "rre1", "hf"))
# per-level variant: run-reduction before the entropy coder — tuned for the
# level-reordered code stream, whose fine-level tail is long same-code runs
register_pipeline("lvl", ("rre4", "hf", "rze1"))


def _resolve(pipeline) -> tuple:
    return get_pipeline(pipeline) if isinstance(pipeline, str) else tuple(pipeline)


def _is_jax(data) -> bool:
    """jax.Array detection without importing jax for host-only callers."""
    return not isinstance(data, np.ndarray) and "jax" in type(data).__module__


def _store_limit(st, cur):
    """Keyword for ``st``'s device twin: the payload size at which the
    stage stores ``cur`` through, from the header (the rule below). A twin
    without the keyword, and an empty stream (never stored through), get
    none."""
    if not cur.size or "limit" not in inspect.signature(st.encode_device).parameters:
        return {}
    return {"limit": lambda hdr: cur.size - len(st.pack_header(hdr))}


def encode(data, pipeline: str | tuple) -> bytes:
    stages = _resolve(pipeline)
    device = _is_jax(data)
    if device:
        from . import engine

        cur = engine.as_device_u8(data)
    else:
        cur = np.ascontiguousarray(data, np.uint8)
    recs = []
    for name in stages:
        st = get_stage(name)
        with span("encode." + name):
            if device and st.encode_device is not None:
                # device uint8 array: the stream stays resident; None: the
                # twin's size alone decided store-through
                nxt, hdr = st.encode_device(cur, **_store_limit(st, cur))
            else:
                if device:  # host-only stage: the stream drops to host for good
                    cur = to_host(cur)
                    device = False
                payload, hdr = st.encode(cur)
                nxt = np.frombuffer(payload, np.uint8) if isinstance(payload, bytes) else payload
            hb = st.pack_header(hdr)
        if nxt is None or (nxt.size + len(hb) >= cur.size and cur.size > 0):
            recs.append((name, 1, b""))  # stage expands: store-through
            count("store_through", 1)
            if nxt is None:
                count("store_through_early", 1)
            continue
        recs.append((name, 0, hb))
        cur = nxt
    out = bytearray(_MAGIC)
    out += struct.pack("<B", len(recs))
    for name, flags, hb in recs:
        nb = name.encode()
        out += struct.pack("<BB", flags, len(nb)) + nb + struct.pack("<I", len(hb)) + hb
    out += to_host(cur).tobytes()
    return bytes(out)


def decode(buf, *, device: bool = False):
    """Decode a pipeline stream back to the uint8 code stream.

    ``buf`` is any bytes-like object (bytes, bytearray, memoryview, uint8
    ndarray) — the v3 frame reader hands memoryviews straight through and
    the payload is sliced, never copied. With ``device=True`` the stream
    decodes through the stages' ``decode_device`` twins, chaining between
    device-capable stages as a device array (a stage without a twin pulls
    the stream to host for that hop), and the return value is a device
    uint8 array; the bytes are identical to the host path either way.
    """
    mv = buf if isinstance(buf, memoryview) else memoryview(buf)
    if mv[:4] == _MAGIC:
        nstages = mv[4]
        off = 5
        recs = []
        for _ in range(nstages):
            flags, nlen = struct.unpack_from("<BB", mv, off)
            off += 2
            name = bytes(mv[off : off + nlen]).decode()
            off += nlen
            (hlen,) = struct.unpack_from("<I", mv, off)
            off += 4
            recs.append((name, flags, bytes(mv[off : off + hlen])))
            off += hlen
        cur = mv[off:]
        for name, flags, hb in reversed(recs):
            if flags & 1:
                continue
            st = get_stage(name)
            hdr = st.unpack_header(hb)
            with span("decode." + name):
                if device and st.decode_device is not None:
                    cur = st.decode_device(cur, hdr)  # device uint8 stream
                    continue
                if _is_jax(cur):  # twin-less stage: pull the stream to host
                    cur = to_host(cur)
                out = st.decode(cur, hdr)
                cur = out.tobytes() if isinstance(out, np.ndarray) else out
    else:
        # legacy stream: u32 length-prefixed JSON meta, dict headers (whose
        # hex-blob fields the twins would host-fallback on anyway)
        mlen = int.from_bytes(mv[:4], "little")
        meta = json.loads(bytes(mv[4 : 4 + mlen]))
        cur = mv[4 + mlen :]
        for name, hdr in zip(reversed(meta["stages"]), reversed(meta["headers"])):
            if hdr.get("_skip"):
                continue
            out = get_stage(name).decode(cur, hdr)
            cur = out.tobytes() if isinstance(out, np.ndarray) else out
    if device:
        from . import engine

        return engine.as_device_u8(cur)
    if _is_jax(cur):
        return to_host(cur).reshape(-1)
    return np.frombuffer(cur, np.uint8)


def encode_v1(data: np.ndarray, pipeline: str | tuple) -> bytes:
    """Legacy (pre-v2) stream writer: JSON meta block with dict headers.

    Kept so tests can fabricate old streams bit-compatibly and so tooling
    can still emit streams readable by pre-registry checkouts.
    """
    stages = _resolve(pipeline)
    cur = np.ascontiguousarray(data, np.uint8)
    headers = []
    for name in stages:
        payload, hdr = get_stage(name).encode(cur)
        # binary header extensions (e.g. hf's "offs" table) can't ride JSON;
        # v1 streams decode through the host reference path without them
        hdr = {k: v for k, v in hdr.items() if not isinstance(v, (bytes, bytearray))}
        nxt = np.frombuffer(payload, np.uint8) if isinstance(payload, bytes) else payload
        if nxt.size + len(json.dumps(hdr)) >= cur.size and cur.size > 0:
            headers.append({"_skip": True})  # stage expands: store-through
            continue
        headers.append(hdr)
        cur = nxt
    meta = json.dumps({"stages": list(stages), "headers": headers}).encode()
    return len(meta).to_bytes(4, "little") + meta + cur.tobytes()
