"""Device-parallel, streaming compression (container v3 producer).

``shard_compress`` splits a field along one axis into per-device chunks and
runs the lossy half of the compressor — block gather, interpolation
prediction (jax or Pallas backend), quantized-code emission — *on the
devices*, under :func:`repro.runtime.partitioning.shard_map`, and then —
new with the device encoding engine (:mod:`repro.core.lossless.engine`) —
keeps the per-shard quantized codes device-resident through block
scatter, level reorder, and the entropy-encoding pipeline. The raw uint8
code stream never crosses to host: what comes back per shard is the
*encoded* frame payload, the (tiny) anchor grid, and the outlier values
(gathered per-shard from the device-resident padded field, never the
field itself), so the ``FrameWriter`` receives ready-to-write frames.
The PR 2/3 orchestration still runs per chunk — each chunk keeps its own
``PredictorPlan`` and lossless-pipeline choice (the orchestrator's
histogram rides the device engine by default) — and the result is framed
as container v3 (:mod:`repro.core.frames`): one complete v1/v2 container
per chunk, independently decodable, CRC-guarded.
``CompressorSpec(engine="numpy")`` opts back into the host reference
encoders (identical bytes either way — the engine's bit-identity
contract).

Bit-identity contract: every frame equals ``Compressor.compress`` of the
same chunk, byte for byte. The per-chunk error bound (rel mode), the
tuning sample (gathered shard-side at exactly the indices the in-process
tuner would draw), the predictor arithmetic, and the container packing all
replicate the single-host path, so ``shard_compress(x)[i]`` ==
``compress(x[i*k:(i+1)*k])`` and any mix of sharded writers and
single-host readers (or vice versa) round-trips.

``chunk_compress`` is the host-sequential twin (same v3 output, no mesh
needed). ``shard_compress`` routes to it, by an up-front choice recorded
in ``last_telemetry["shard"]``, for non-divisible axes, 1-device meshes,
predictors without a device path and fields holding NaN/Inf; it is also
the checkpoint codec's streaming producer. A failure of the device passes
raises. ``shard_decompress`` reads any v3 chunk stream,
optionally with a thread pool (frames decode independently, so decode
parallelism is embarrassing).
"""
from __future__ import annotations

import io

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import blocks as blk
from . import frames
from .autotune import levels_for_stride, legacy_sample_indices, plan_sample_indices
from . import compressor as _compressor_mod
from .compressor import Compressor, CompressorSpec, _sections_pack
from .predictor import compress_blocks, quant_steps
from .stencils import build_steps

_AXIS = "shards"


def default_mesh(devices=None) -> Mesh:
    """1-D compression mesh over the host's devices (axis ``"shards"``)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), (_AXIS,))


def _resolve_compressor(spec, compressor, kw) -> Compressor:
    if compressor is not None:
        return compressor
    return Compressor(spec, **kw) if spec is not None or kw else Compressor(CompressorSpec())


def _chunk_header(x_shape, axis: int, sizes, spec: CompressorSpec) -> dict:
    return {
        "kind": "chunks",
        "version": 3,
        "shape": list(x_shape),
        "axis": int(axis),
        "chunk_sizes": [int(s) for s in sizes],
        "eb_mode": spec.eb_mode,
    }


# ------------------------------------------------------------- device helpers
def _pad_field_batch_jnp(xb, stride: int):
    """jnp twin of blocks.pad_field_batch (edge-replicate to the block grid)."""
    tgt = blk.padded_shape(xb.shape[1:], stride)
    pads = [(0, 0)] + [(0, t - s) for s, t in zip(xb.shape[1:], tgt)]
    if all(p == (0, 0) for p in pads[1:]):
        return xb
    return jnp.pad(xb, pads, mode="edge")


# moved to blocks.py so the decompress device tail shares it; the old name
# stays importable for existing callers
_gather_blocks_jnp = blk.gather_blocks_batch_jnp


def _fold_chunk(chunk):
    """jnp twin of Compressor._spatial_view: fold to (batch, spatial<=3)."""
    nd = min(chunk.ndim, 3)
    spatial = chunk.shape[chunk.ndim - nd :]
    batch = int(np.prod(chunk.shape[: chunk.ndim - nd], dtype=np.int64)) if chunk.ndim > nd else 1
    return chunk.reshape((batch,) + spatial), spatial


def _predict_codes(blocks, twoeb, inv2eb, steps, stride: int, ndim: int, backend: str):
    """Fused predict+quantize on the device shard (jax or Pallas kernel)."""
    if backend == "pallas" and ndim == 3:
        from repro.kernels.interp3d.interp3d import LANES, interp3d_compress

        nbk = blocks.shape[0]
        lane_pad = (-nbk) % LANES
        if lane_pad:
            blocks = jnp.concatenate([blocks, jnp.zeros((lane_pad,) + blocks.shape[1:], blocks.dtype)], 0)
        bt = jnp.moveaxis(blocks, 0, -1)  # (B,B,B,nb') — block axis on lanes
        interpret = jax.default_backend() != "tpu"
        codes, _ = interp3d_compress(bt, twoeb, inv2eb, steps, stride, interpret)
        return jnp.moveaxis(codes, -1, 0)[:nbk]
    codes, _, _ = compress_blocks(blocks, twoeb, inv2eb, steps, stride)
    return codes


def _shard_slices(arr) -> dict:
    """Map chunk index (along dim 0 of a P('shards')-sharded array) ->
    single-device shard data, deduping replicated placements."""
    out = {}
    for s in arr.addressable_shards:
        start = s.index[0].start or 0
        out.setdefault(start, s.data)
    return out


def _gather_flat(dev_arr, oi: np.ndarray) -> np.ndarray:
    """Pull only ``oi`` positions of a device-resident array to host."""
    if oi.size == 0:
        return np.zeros(0, np.float32)
    vals = jnp.asarray(dev_arr).reshape(-1)[jnp.asarray(oi)]
    return np.asarray(vals, np.float32)


# ------------------------------------------------------------ host twin
def chunk_compress(x, *, axis: int = 0, n_chunks: int | None = None,
                   spec: CompressorSpec | None = None, compressor: Compressor | None = None,
                   out=None, sync: bool = False, **kw) -> bytes | int:
    """Host-sequential v3 producer: split along ``axis``, one container
    frame per chunk (``Compressor.compress`` of the chunk, bit for bit).

    ``out``: optional file-like sink — frames are written (and flushed) as
    each chunk's encode completes, so a slow sink overlaps the next
    chunk's encode; returns the frame count then. Without ``out`` returns
    the packed v3 bytes. ``sync=True`` writes per-frame sync markers +
    sequence numbers (O(damage) resync, exact surviving-frame indices —
    see :mod:`repro.core.frames`); the default layout is unchanged. If the
    encode of a chunk fails mid-stream, the writer *aborts* (no trailer),
    so the partial stream reads as truncated instead of complete.
    """
    comp = _resolve_compressor(spec, compressor, kw)
    x = np.asarray(x)
    n = x.shape[axis]
    n_chunks = max(1, min(n, n_chunks if n_chunks is not None else 1))
    bounds = np.linspace(0, n, n_chunks + 1).astype(np.int64)
    sizes = np.diff(bounds)
    sink = out if out is not None else io.BytesIO()
    hold, comp._telemetry_hold = comp._telemetry_hold, True
    if not hold:  # a holding caller (shard_compress) keeps its records
        comp.last_telemetry = None
    try:
        with frames.FrameWriter(sink, _chunk_header(x.shape, axis, sizes, comp.spec), sync=sync) as w:
            sl = [slice(None)] * x.ndim
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                sl[axis] = slice(int(lo), int(hi))
                w.write_frame(comp.compress(x[tuple(sl)]))
        nf = w.close()
    finally:
        comp._telemetry_hold = hold
    return nf if out is not None else sink.getvalue()


# ------------------------------------------------------------ sharded path
def shard_compress(x, mesh: Mesh | None = None, *, axis: int = 0,
                   spec: CompressorSpec | None = None, compressor: Compressor | None = None,
                   out=None, sync: bool = False, **kw):
    """Device-parallel v3 producer (see module docstring).

    ``x``: array (numpy or jax, possibly already device-sharded) or a
    pytree of arrays — a pytree maps to a same-structure pytree of v3
    containers. ``mesh``: a 1-D mesh; defaults to all local devices.
    Chunks = equal splits of ``x.shape[axis]`` across the mesh. Runs
    :func:`chunk_compress` (identical container format) instead when the
    axis doesn't split evenly, the mesh is a single device, the spec's
    predictor has no device path, or the field holds NaN/Inf (the device
    passes have no nfsafe stage). ``last_telemetry["shard"]`` records the
    path taken (``"shard_map"`` or ``"chunk_compress"``, with the reason).
    A failure of the device passes raises. ``out``: optional file-like
    sink, frames stream to it as encoded (returns the frame count).
    ``sync=True`` adds per-frame sync markers (see
    :mod:`repro.core.frames`).
    """
    if not isinstance(x, (np.ndarray, jnp.ndarray)):
        if out is not None:
            raise ValueError("out= takes a single container; it cannot hold a pytree of leaves — "
                             "stream each leaf separately")

        def one(leaf):
            arr = np.asarray(leaf)
            if arr.ndim == 0:  # scalar leaves (step counters, ...) are not fields
                raise TypeError(
                    f"shard_compress pytree leaves must be arrays with ndim >= 1, got "
                    f"{type(leaf).__name__} shaped {arr.shape}; filter scalar leaves out first"
                )
            return shard_compress(arr, mesh, axis=axis, spec=spec, compressor=compressor,
                                  sync=sync, **kw)

        return jax.tree.map(one, x)
    comp = _resolve_compressor(spec, compressor, kw)
    sp = comp.spec
    mesh = mesh if mesh is not None else default_mesh()
    if len(mesh.axis_names) != 1:
        raise ValueError(f"shard_compress needs a 1-D mesh, got axes {mesh.axis_names}")
    ndev = int(np.prod(mesh.devices.shape))
    n = int(x.shape[axis])
    hold, comp._telemetry_hold = comp._telemetry_hold, True
    if not hold:
        comp.last_telemetry = None

    def host_twin(reason: str, n_chunks: int):
        comp._telemetry()["shard"] = {"path": "chunk_compress", "reason": reason, "ndev": ndev}
        return chunk_compress(np.asarray(x), axis=axis, n_chunks=n_chunks,
                              compressor=comp, out=out, sync=sync)

    try:
        if ndev == 1:
            return host_twin("one device", 1)
        if n % ndev:
            return host_twin(f"axis {n} not divisible by {ndev}", min(n, ndev))
        if sp.predictor not in ("interp", "auto"):
            return host_twin(f"predictor {sp.predictor!r} has no device path", min(n, ndev))
        k = n // ndev
        chunk_shape = tuple(k if d == axis else s for d, s in enumerate(x.shape))
        xd, mn, mx, samples = _shard_pass_a(x, mesh, axis, chunk_shape, comp)
        # non-finite ingest: NaN/Inf anywhere in a chunk poisons its min/max
        # (jnp reductions propagate), so this one check covers the whole
        # field; chunk_compress's per-chunk Compressor.compress runs the
        # nfsafe canonicalization (bitmap + fill)
        if not (np.isfinite(mn).all() and np.isfinite(mx).all()):
            return host_twin("non-finite values", ndev)
        comp._telemetry()["shard"] = {"path": "shard_map", "ndev": ndev}
        # _shard_compress_frames is a generator: each chunk's host tail
        # (scatter/orchestrate/encode) yields its frame as soon as it is
        # packed, so sink writeback overlaps the next chunk's encode.
        gen = _shard_compress_frames(xd, mn, mx, samples, mesh, axis, ndev, k, chunk_shape, comp)
        sink = out if out is not None else io.BytesIO()
        with frames.FrameWriter(sink, _chunk_header(x.shape, axis, [k] * ndev, sp),
                                sync=sync) as w:
            for fr in gen:
                w.write_frame(fr)
        nf = w.close()
    finally:
        comp._telemetry_hold = hold
    return nf if out is not None else sink.getvalue()


def _chunk_geometry(chunk_shape, sp):
    """Static per-chunk geometry (chunks are uniform): (nd, cb,
    padded_shapes, nblocks, tune, sample_idx)."""
    nd = min(len(chunk_shape), 3)
    spatial = chunk_shape[len(chunk_shape) - nd :]
    cb = int(np.prod(chunk_shape[: len(chunk_shape) - nd], dtype=np.int64)) if len(chunk_shape) > nd else 1
    padded_shapes = blk.padded_shape(spatial, blk.ANCHOR_STRIDE)
    nblocks = cb * int(np.prod(blk.block_grid(padded_shapes, blk.ANCHOR_STRIDE)))
    tune = sp.predictor == "auto" or (sp.predictor == "interp" and sp.autotune)
    sample_idx = (plan_sample_indices if sp.predictor == "auto" else legacy_sample_indices)(nblocks)
    return nd, cb, padded_shapes, nblocks, tune, sample_idx


def _shard_pass_a(x, mesh, axis, chunk_shape, comp):
    """Place ``x`` on the mesh and run pass A: per-chunk min/max (the rel
    bound) and the shard-side tuning sample. Returns (xd, mn, mx, samples)
    with the last three on host."""
    from repro.runtime.partitioning import shard_map

    aname = mesh.axis_names[0]
    spec_sharded = P(*(aname if d == axis else None for d in range(len(chunk_shape))))
    x = x.astype(jnp.float32) if isinstance(x, jax.Array) else np.asarray(x, np.float32)
    xd = jax.device_put(x, NamedSharding(mesh, spec_sharded))  # host shards go straight to their device
    _, _, _, _, tune, sample_idx = _chunk_geometry(chunk_shape, comp.spec)

    def body_a(chunk):
        xb, _ = _fold_chunk(chunk)
        mn = jnp.min(xb).reshape(1) if xb.size else jnp.zeros(1)
        mx = jnp.max(xb).reshape(1) if xb.size else jnp.zeros(1)
        padded = _pad_field_batch_jnp(xb, blk.ANCHOR_STRIDE)
        blocks = _gather_blocks_jnp(padded, blk.ANCHOR_STRIDE)
        sample = blocks[jnp.asarray(sample_idx)] if tune else jnp.zeros((1,) + blocks.shape[1:])
        return mn, mx, sample

    fa = shard_map(body_a, mesh, in_specs=(spec_sharded,), out_specs=(P(aname),) * 3)
    mn, mx, samples = jax.jit(fa)(xd)
    return xd, np.asarray(mn), np.asarray(mx), np.asarray(samples)


def _shard_compress_frames(xd, mn, mx, samples, mesh, axis, ndev, k, chunk_shape, comp):
    sp = comp.spec
    aname = mesh.axis_names[0]
    spec_sharded = P(*(aname if d == axis else None for d in range(len(chunk_shape))))
    scalar_spec = P(aname)
    scalar_sharding = NamedSharding(mesh, scalar_spec)
    from repro.runtime.partitioning import shard_map

    nd, cb, padded_shapes, nblocks, tune, sample_idx = _chunk_geometry(chunk_shape, sp)
    ns = sample_idx.size if tune else 1

    # ---- per-chunk eb + tuning (host; the sample is all it needs)
    eb_abs = np.empty(ndev, np.float64)
    tuned = []
    for i in range(ndev):
        if sp.eb_mode == "abs":
            eb_abs[i] = float(sp.eb)
        else:
            # f64 subtraction: a float32 mx-mn of an extreme-range chunk
            # overflows to inf and poisons the bound
            eb_abs[i] = float(sp.eb) * (float(mx[i]) - float(mn[i]))
        if eb_abs[i] == 0.0:
            tuned.append(None)  # constant chunk: framed via the const path
            continue
        if tune:
            chunk_sample = samples[i * ns : (i + 1) * ns]
            tuned.append(comp._tune_interp(chunk_sample, eb_abs[i], cb, padded_shapes,
                                           presampled_of=nblocks))
        else:
            levels = levels_for_stride(sp.anchor_stride)
            tuned.append((sp.anchor_stride, tuple(sp.splines[: len(levels)]), tuple(sp.schemes[: len(levels)])))

    # ---- pass B: predict+quantize per plan group (static step tables).
    # Step tables are static to the trace, so shards whose tuners picked
    # different plans cannot share one shard_map call: each distinct plan
    # re-runs the pass over the whole mesh and keeps only its members'
    # outputs. Homogeneous data (the common case) is a single pass; N
    # heterogeneous plans cost N passes — acceptable for now, revisit with
    # stacked per-shard step operands if mixed-plan fields become hot.
    groups: dict[tuple, list[int]] = {}
    for i, t in enumerate(tuned):
        if t is not None:
            groups.setdefault(t, []).append(i)
    use_dev = sp.engine != "numpy"  # auto/device: codes never visit host
    codes_np = None if use_dev else np.empty((ndev * nblocks,) + (blk.BLOCK,) * nd, np.uint8)
    codes_dev: dict[int, object] = {}
    anc_np: dict[int, np.ndarray] = {}
    padded_shards: dict[int, object] = {}
    for (stride, splines, schemes), members in groups.items():
        steps = build_steps(nd, blk.BLOCK, levels_for_stride(stride), splines, schemes)
        twoeb, inv2eb = quant_steps([eb_abs[i] if i in members else 0.5 for i in range(ndev)])

        def body_b(chunk, t2, i2):
            xb, _ = _fold_chunk(chunk)
            padded = _pad_field_batch_jnp(xb, blk.ANCHOR_STRIDE)
            blocks = _gather_blocks_jnp(padded, blk.ANCHOR_STRIDE)
            codes = _predict_codes(blocks, t2[0], i2[0], steps, stride, nd, sp.backend)
            anc_sl = (slice(None),) + tuple(slice(None, None, stride) for _ in range(nd))
            return codes.astype(jnp.uint8), padded[anc_sl], padded

        fb = shard_map(body_b, mesh, in_specs=(spec_sharded, scalar_spec, scalar_spec),
                       out_specs=(scalar_spec,) * 3)
        td, id_ = (jax.device_put(jnp.asarray(a), scalar_sharding) for a in (twoeb, inv2eb))
        codes_g, anc_g, padded_g = jax.jit(fb)(xd, td, id_)
        anc_host = np.asarray(anc_g)
        pslices = _shard_slices(padded_g)
        per_anc = anc_host.shape[0] // ndev
        if use_dev:
            cslices = _shard_slices(codes_g)  # per-shard device arrays
        else:
            codes_host = np.asarray(codes_g)
        for i in members:
            if use_dev:
                codes_dev[i] = cslices.get(i * nblocks)
            else:
                codes_np[i * nblocks : (i + 1) * nblocks] = codes_host[i * nblocks : (i + 1) * nblocks]
            anc_np[i] = anc_host[i * per_anc : (i + 1) * per_anc]
            padded_shards[i] = pslices.get(i * cb)

    # ---- per-chunk tail: scatter + level reorder + entropy encode run on
    # the shard's device under engine="auto"/"device" (the raw uint8 code
    # stream never crosses to host — only the encoded frame payload does,
    # via _pack_interp); engine="numpy" replays the host reference path.
    # Frames are yielded one at a time so the caller can write frame i
    # while frame i+1 encodes.
    for i in range(ndev):
        base_hdr = {
            "shape": list(chunk_shape),
            "predictor": sp.predictor,
            "eb_abs": eb_abs[i],
            "anchor_stride": sp.anchor_stride,
        }
        if tuned[i] is None:  # constant chunk — value fetched from the shard
            yield _sections_pack(dict(base_hdr, mode="const"),
                                 [np.float32(_first_value(xd, i, k, axis)).tobytes()])
            continue
        stride, splines, schemes = tuned[i]
        if use_dev:
            cgrid = blk.scatter_blocks_batch_jnp(jnp.asarray(codes_dev[i]), blk._scatter_index(padded_shapes),
                                                 cb, padded_shapes)
            if _compressor_mod._CODE_FAULT is not None:
                # test-only encoder-fault hook (see testing.faults): worth a
                # device round trip only when armed
                cgrid = jnp.asarray(comp._maybe_fault_codes(np.asarray(cgrid)))
            oi = np.asarray(jnp.flatnonzero(cgrid.reshape(-1) == 0)).astype(np.int64)
        else:
            cgrid = blk.scatter_blocks_batch(codes_np[i * nblocks : (i + 1) * nblocks],
                                             cb, padded_shapes, blk.ANCHOR_STRIDE)
            cgrid = comp._maybe_fault_codes(cgrid)
            oi = np.flatnonzero(cgrid.reshape(-1) == 0).astype(np.int64)  # code 0 == outlier
        ov = _gather_flat(padded_shards[i], oi)
        fr = comp._pack_interp(base_hdr, cgrid=cgrid, anc=anc_np[i], oi=oi, ov=ov,
                               stride=stride, splines=splines, schemes=schemes)
        if sp.verify != "off":
            # the bound check the host path runs inside compress(): decode
            # the fresh frame and verify against this chunk's bound; a
            # violation repairs through the host re-encode ladder (frame
            # stays a valid standalone container) or raises the typed
            # BoundViolationError. The chunk slice crosses to host only
            # under verify — engine residency is unchanged otherwise.
            sl = tuple(slice(i * k, (i + 1) * k) if d == axis else slice(None)
                       for d in range(xd.ndim))
            chunk_host = np.ascontiguousarray(np.asarray(xd[sl]), np.float32)
            fr = comp._verify_repair(chunk_host, fr, bound=float(eb_abs[i]), rel=False)
        yield fr


def _first_value(xd, i: int, k: int, axis: int) -> float:
    """First element of chunk ``i`` (the const-mode fill), fetched without
    pulling the chunk to host."""
    if any(d == 0 for d in xd.shape):
        return 0.0
    idx = tuple(i * k if d == axis else 0 for d in range(xd.ndim))
    return float(jnp.asarray(xd[idx]))


# --------------------------------------------------------------- decompress
def _decode_workers() -> int:
    """Frame-decode thread count: REPRO_DECODE_WORKERS env override, else 1.

    The default stays sequential (thread fan-out is a policy the caller or
    the environment opts into — CI pins the env for determinism); any
    positive value sizes the per-call thread pool in shard_decompress.
    """
    import os

    try:
        env = int(os.environ.get("REPRO_DECODE_WORKERS", "0"))
    except ValueError:
        env = 0
    return env if env > 0 else 1


def shard_decompress(buf, frames_sel=None, *, workers: int | None = None,
                     on_error: str = "raise", fill_value: float = 0.0,
                     compressor: Compressor | None = None, out: str = "numpy"):
    """Decode a v3 chunk stream; ``frames_sel`` selects a subset (any order).

    ``workers > 1`` decodes frames on a thread pool — frames are
    independent containers, so decode parallelism needs no coordination;
    with ``out="device"`` each worker decodes its frame straight onto the
    device (host I/O and device decode overlap across frames) and the
    chunks concatenate device-side. ``workers=None`` reads the
    ``REPRO_DECODE_WORKERS`` env override (default 1, sequential).

    ``on_error="skip"``/``"fill"``: salvage decode of damaged streams,
    same semantics as :meth:`Compressor.decompress` — damaged chunks are
    dropped or filled, intact chunks decode normally. Pass your own
    ``compressor`` to read the damage mask back from its ``last_damage``.
    """
    comp = compressor if compressor is not None else Compressor(CompressorSpec())
    if workers is None:
        workers = _decode_workers()
    if workers <= 1:
        return comp.decompress(buf, frames=frames_sel, on_error=on_error,
                               fill_value=fill_value, out=out)
    comp.last_damage = None
    header, payloads, report = comp._salvage_payloads(buf, on_error)
    if header.get("kind") != "chunks":
        raise ValueError(f"v3 container kind {header.get('kind')!r} is not a compressor chunk stream")
    n_chunks = len(header["chunk_sizes"])
    idx = list(range(n_chunks)) if frames_sel is None else [int(i) for i in frames_sel]
    if not idx:
        raise ValueError("frames_sel selected no frames; pass at least one index (or None for all)")
    from concurrent.futures import ThreadPoolExecutor

    from .errors import ContainerError

    def _one(i: int):
        p = payloads.get(i)
        if p is None:
            if on_error == "raise":
                raise ContainerError(f"frame {i} missing from v3 container")
            return None
        try:
            return comp.decompress(p, out=out)
        except Exception as e:
            if on_error == "raise":
                raise
            report.add("decode", -1, index=i, detail=repr(e))
            report.frames_damaged += 1
            return None

    hold, comp._telemetry_hold = comp._telemetry_hold, True
    if not hold:
        comp.last_telemetry = None
    try:
        with ThreadPoolExecutor(max_workers=workers) as ex:
            raw = list(ex.map(_one, idx))
    finally:
        comp._telemetry_hold = hold
    mask = [p is not None for p in raw]
    parts = []
    for i, p in zip(idx, raw):
        if p is not None:
            parts.append(p)
        elif on_error == "fill":
            parts.append(np.full(Compressor._chunk_shape(header, i), np.float32(fill_value), np.float32))
    if not report.ok:
        comp.last_damage = {"report": report, "chunks_ok": mask, "on_error": on_error}
    if not parts:
        raise ContainerError(f"no decodable frames in damaged v3 container ({report.summary()})")
    axis = int(header.get("axis", 0))
    if len(parts) == 1:
        result = parts[0]
    else:
        result = jnp.concatenate(parts, axis=axis) if out == "device" else np.concatenate(parts, axis=axis)
    if out == "device" and isinstance(result, np.ndarray):
        result = jnp.asarray(result)
    return result
