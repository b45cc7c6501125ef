"""cuSZ-Hi top-level compressor (the paper's full pipeline, §4-§5).

compress():  pad -> [autotune] -> interpolation predict+quantize (blocks,
jit/Pallas) -> scatter codes -> level-reorder (Eq.3) -> lossless pipeline
-> container with anchors + outliers.  decompress() replays the identical
arithmetic from the codes.

The lossy seam mirrors the lossless one: ``CompressorSpec.predictor``
accepts ``"auto"``, which runs the per-level planner
(repro.core.autotune.autotune_plan) over sampled anchor blocks — candidate
splines (linear / cubic / natural-cubic), interpolation schemes ("md" vs
per-dimension sequential orderings) and anchor strides, scored by
quantized-residual entropy through the same stream_stats cost model the
lossless orchestrator uses. The winning ``PredictorPlan`` drives the step
tables (jax and Pallas backends alike) and is serialized into the
container v2 header as the (anchor_stride, splines, schemes) fields —
zero overhead over a fixed spec; ``Compressor.inspect`` surfaces it as
``pplan``. v1/v2 containers without recorded splines/schemes decode with
the default cubic/md steps.

The lossless seam rides the stage registry (repro.core.lossless.stages /
pipelines): ``CompressorSpec.pipeline`` names any registered pipeline
(CR: hf-rre4-tcms8-rze1 / TP: tcms1-bit1-rre1 / ...), and ``"auto"``
invokes the orchestrator (repro.core.lossless.orchestrate), which samples
the quantization-code stream, scores every registered pipeline with the
stage cost hooks plus a trial encode, and picks the best fit per field.
The chosen pipeline name and the sampled statistics are recorded in the
container header, so decompression never re-infers anything.

Container format v2 (binary): ``CSZH2\\n`` magic, u32 header length, a
compact binary header (repro.core.serial), then a section table — u32
section count + u64 sizes — followed by the section bytes. Containers
written by earlier checkouts (``CSZH1\\n`` magic + JSON header, JSON-meta
lossless streams) still decompress bit-exactly through the v1 read path.
Container v3 (``CSZH3\\n``, repro.core.frames) frames a field as
independently decodable chunks — each frame is a complete v1/v2 container
of one chunk, CRC-guarded — written by ``repro.core.distributed`` for
sharded/streaming compression; ``decompress(buf, frames=[...])`` decodes
any subset in any order.
Spec validation happens at construction: unknown pipeline/backend/
predictor names raise immediately, listing the registered names.

Error-bound contract: ||x - decompress(compress(x))||_inf <= eb_abs,
where eb_abs = eb * value_range(x) in the paper's default "rel" mode.

Hot-path architecture: the whole compressor is *batched end-to-end*. Fields
with leading batch dimensions are folded to (batch, spatial<=3) once;
padding, block gather/scatter, the level reorder (cached permutation
gathers), anchor extraction and outlier collection are all single
vectorized numpy ops over the batch axis, the predictor runs as ONE jitted
device call over the concatenated block axis, and the quantization codes of
the whole batch are emitted as ONE code sequence into a single
``pipelines.encode`` call — no per-item Python loops, one host<->device
round-trip per field.

The predictor backend is selected by ``CompressorSpec.backend``:
``"jax"`` (default) uses the pure-jnp engine in repro.core.predictor;
``"pallas"`` routes compression through the fused Pallas TPU kernel in
repro.kernels.interp3d (interpret mode off-TPU, compiled on TPU; 3-D
fields only — other ranks run the jax engine). Decompression always
replays through the jax engine; both backends run the same f32 operation
sequence, so the error-bound contract holds either way.

A device, Pallas or shard failure raises to the caller: there is no
silent retry on another implementation. Which engine runs is a choice
made up front (``CompressorSpec.engine``), recorded in
``last_telemetry``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import struct
import threading
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as blk
from . import frames as frames_mod
from . import lorenzo as lor
from . import spans
from .errors import BoundViolationError, ContainerError, DamageReport, FrameCRCError, SpecError
from .retry import RetryPolicy
from .autotune import (
    DEFAULT_STRIDES,
    PredictorPlan,
    autotune,
    autotune_plan,
    levels_for_stride,
    plan_signature,
    stats_bucket,
)
from .lossless import orchestrate, pipelines
from .lossless.flenc import fl_decode, fl_encode
from .predictor import ARITH, compress_blocks, decompress_blocks, decompress_blocks_matmul, quant_steps
from .reorder import _restore_gather, reorder_codes_batch, restore_codes_batch, restore_codes_batch_device
from .serial import pack_obj, unpack_obj
from .stencils import SPLINES, build_steps

MAGIC_V1 = b"CSZH1\n"
MAGIC = b"CSZH2\n"
MAGIC_V3 = frames_mod.MAGIC_V3  # chunked frame streams (repro.core.frames)

_PREDICTORS = ("interp", "auto", "lorenzo", "offset1d")
_BACKENDS = ("jax", "pallas")
_ENGINES = ("auto", "numpy", "device")
_EB_MODES = ("rel", "abs", "pw_rel")
_VERIFY_MODES = ("off", "sample", "full")
_ANCHOR_STRIDES = (4, 8, 16)  # power-of-two strides the 17^ndim block supports

# Bound-verification knobs: "sample" checks at most this many points
# (deterministic stride sample over the flat field), the repair ladder
# re-encodes at a halved bound up to `attempts` times before raising
# BoundViolationError (core/retry.py policy shape: no sleeping — repair
# is CPU work, not a flaky transport).
_VERIFY_SAMPLE = 1 << 16
_REPAIR_POLICY = RetryPolicy(attempts=3, base_delay=0.0, max_delay=0.0, jitter=0.0,
                             retry_on=(BoundViolationError,))
_REPAIR_TIGHTEN = 0.5
# Enforcement slack: quantization guarantees err <= eb in exact arithmetic,
# but f32 reconstruction rounds — a clean encode can land a point at
# eb * (1 + few-ulp). The systemwide contract (tests, benches) already
# allows 1e-4 relative; enforcing tighter here would "repair" correct
# containers at a real CR cost. Genuine violations (a wrong code is >= 2eb
# off) clear this slack by orders of magnitude.
_VERIFY_SLACK = 1e-4

# Test-only fault hook (repro.testing.faults.perturb_quant_codes): called
# with the quantization-code block batch right after the predictor, before
# reorder/encode — lets the chaos suite inject a real bound violation that
# verify= must catch. None in production.
_CODE_FAULT = None

# ---------------------------------------------------------------- spec grammar
# Canonical compression-spec string grammar (the single spec entry point
# shared by repro.io, the compressd protocol, `serve --kv-spec`, the
# checkpoint codec's REPRO_CKPT_SPEC, and the benches):
#
#     "lossy" "," <eb_mode> "," <number> { "," key "=" value }
#     "lossy" "," "psnr"    "," <target_dB> { "," key "=" value }
#
# e.g. "lossy,abs,1e-3,predictor=auto" or "lossy,psnr,60,pipeline=cr".
# Tuple-valued keys join their items with ":" ("splines=cubic:linear"),
# booleans are "true"/"false". `CompressorSpec.to_string()` emits the
# canonical form (head + sorted non-default key=value pairs), and
# `from_string(to_string(spec)) == spec` for every valid spec. The
# dataset-level "lossless[,...]" form is handled by repro.io (raw-chunk
# storage); it is not a CompressorSpec.
_SPEC_TUPLE_FIELDS = {"splines", "schemes", "pipeline_candidates", "plan_anchor_strides"}
_SPEC_BOOL_FIELDS = {"autotune", "reorder"}


def _spec_parse_value(key: str, raw: str):
    """Parse one ``key=value`` token of the spec grammar into the typed
    CompressorSpec field value; raises :class:`SpecError` on bad syntax."""
    if key in _SPEC_BOOL_FIELDS:
        low = raw.strip().lower()
        if low in ("true", "1", "yes", "on"):
            return True
        if low in ("false", "0", "no", "off"):
            return False
        raise SpecError(f"spec key {key!r} expects a boolean, got {raw!r}")
    if key in _SPEC_TUPLE_FIELDS:
        items = tuple(t.strip() for t in raw.split(":") if t.strip())
        if not items:
            raise SpecError(f"spec key {key!r} expects ':'-joined items, got {raw!r}")
        if key == "plan_anchor_strides":
            try:
                return tuple(int(t) for t in items)
            except ValueError as e:
                raise SpecError(f"spec key {key!r} expects integers, got {raw!r}") from e
        return items
    if key == "anchor_stride":
        try:
            return int(raw)
        except ValueError as e:
            raise SpecError(f"spec key {key!r} expects an integer, got {raw!r}") from e
    if key in ("eb", "psnr_target"):
        try:
            return float(raw)
        except ValueError as e:
            raise SpecError(f"spec key {key!r} expects a number, got {raw!r}") from e
    return raw.strip()


def _spec_format_value(key: str, value) -> str:
    if key in _SPEC_BOOL_FIELDS:
        return "true" if value else "false"
    if key in _SPEC_TUPLE_FIELDS:
        return ":".join(str(v) for v in value)
    if isinstance(value, float):
        return repr(value)  # shortest round-tripping float repr
    return str(value)


@dataclasses.dataclass(frozen=True)
class CompressorSpec:
    eb: float = 1e-3
    eb_mode: str = "rel"                  # "rel": eb * value range (paper); "abs"
    predictor: str = "interp"             # interp | auto (plan-driven) | lorenzo | offset1d
    pipeline: str = "cr"                  # any registered pipeline, or "auto"
    anchor_stride: int = 16               # 16 = cuSZ-Hi; 8 = cuSZ-I layout
    autotune: bool = True
    splines: tuple = ("cubic", "cubic", "cubic", "cubic")
    schemes: tuple = ("md", "md", "md", "md")
    reorder: bool = True
    backend: str = "jax"                  # jax | pallas (fused interp3d kernel)
    # lossless encoding engine (repro.core.lossless.engine): "numpy" runs the
    # reference host stages, "device" keeps the code stream on device through
    # scatter/reorder/entropy-encode (jit/Pallas stage kernels), "auto" uses
    # the device engine exactly when the stream is already device-resident
    # (the sharded path) and the host path otherwise. All three produce
    # byte-identical containers — the engine carries a bit-identity contract.
    engine: str = "auto"
    # pipeline="auto" only: restrict the orchestrator's search space, e.g. to
    # orchestrate.portable_pipelines() for artifacts that must restore on any
    # machine. None = every registered pipeline.
    pipeline_candidates: tuple | None = None
    # predictor="auto" only: anchor strides the planner explores.
    plan_anchor_strides: tuple = DEFAULT_STRIDES
    # PSNR-target mode: instead of a fixed bound, binary-search the abs eb
    # over a sampled trial compress until the reconstruction PSNR lands on
    # this target (dB). The searched eb_abs is recorded in the container
    # header like any other, so decode is oblivious. Mutually exclusive
    # with eb_mode="pw_rel" (the search runs in the abs-bound domain).
    psnr_target: float | None = None
    # Post-compression bound verification: "sample" (default) decodes the
    # fresh container and checks the error bound on a deterministic point
    # sample, "full" checks every point, "off" trusts the encoder (the
    # pre-PR-10 behavior). A violation auto-repairs: re-encode at a
    # tightened bound under a bounded retry ladder, recorded in
    # last_telemetry["verify"]; BoundViolationError only when exhausted.
    verify: str = "sample"

    def __post_init__(self):
        if self.verify not in _VERIFY_MODES:
            raise ValueError(f"unknown verify mode {self.verify!r}; one of {_VERIFY_MODES}")
        if self.pipeline != "auto" and self.pipeline not in pipelines.PIPELINES:
            raise ValueError(
                f"unknown pipeline {self.pipeline!r}; registered pipelines: "
                f"{', '.join(sorted(pipelines.PIPELINES))} (or 'auto')"
            )
        if self.pipeline_candidates is not None and not self.pipeline_candidates:
            raise ValueError("pipeline_candidates must be None or a non-empty sequence of pipeline names")
        for nm in self.pipeline_candidates or ():
            pipelines.get_pipeline(nm)  # raises with the registered list
        if self.predictor not in _PREDICTORS:
            raise ValueError(f"unknown predictor {self.predictor!r}; one of {_PREDICTORS}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; one of {_BACKENDS}")
        if self.engine not in _ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; one of {_ENGINES}")
        if self.eb_mode not in _EB_MODES:
            raise ValueError(f"unknown eb_mode {self.eb_mode!r}; one of {_EB_MODES}")
        for st in (self.anchor_stride,) + tuple(self.plan_anchor_strides):
            if st not in _ANCHOR_STRIDES:
                raise ValueError(f"unsupported anchor stride {st}; one of {_ANCHOR_STRIDES}")
        for s in self.splines:
            if s not in SPLINES:
                raise ValueError(f"unknown spline {s!r}; one of {SPLINES}")
        for s in self.schemes:
            if s != "md" and s != "1d" and not s.startswith("1d-"):
                raise ValueError(f"unknown scheme {s!r}; 'md', '1d', or '1d-<perm>'")
        if self.eb_mode == "pw_rel" and not (self.eb > 0):
            raise ValueError(f"eb_mode='pw_rel' needs eb > 0, got {self.eb}")
        if self.psnr_target is not None:
            if not (float(self.psnr_target) > 0) or not np.isfinite(self.psnr_target):
                raise ValueError(f"psnr_target must be a positive finite dB value, got {self.psnr_target}")
            if self.eb_mode == "pw_rel":
                raise ValueError("psnr_target is incompatible with eb_mode='pw_rel' "
                                 "(the eb search runs in the abs-bound domain)")

    @property
    def levels(self) -> tuple:
        return levels_for_stride(self.anchor_stride)

    # ------------------------------------------------------- spec strings
    @classmethod
    def from_string(cls, spec: str) -> "CompressorSpec":
        """Parse the canonical compression-spec grammar (module comment
        above): ``"lossy,<eb_mode>,<eb>[,key=value...]"`` or
        ``"lossy,psnr,<target>[,key=value...]"``. Raises
        :class:`repro.core.errors.SpecError` (a ``ValueError``) for bad
        grammar, unknown keys, or values the spec rejects."""
        parts = [p.strip() for p in str(spec).split(",")]
        if not parts or not parts[0]:
            raise SpecError("empty compression spec")
        if parts[0] == "lossless":
            raise SpecError(
                "'lossless' is a dataset-level spec (raw chunk storage, see repro.io); "
                "CompressorSpec is error-bounded — use 'lossy,<mode>,<eb>'")
        if parts[0] != "lossy":
            raise SpecError(f"compression spec must start with 'lossy', got {parts[0]!r} "
                            f"(full spec: {spec!r})")
        if len(parts) < 3:
            raise SpecError(f"lossy spec needs 'lossy,<mode>,<value>', got {spec!r}")
        mode = parts[1]
        kw: dict = {}
        if mode == "psnr":
            kw["psnr_target"] = _spec_parse_value("psnr_target", parts[2])
        elif mode in _EB_MODES:
            kw["eb_mode"] = mode
            kw["eb"] = _spec_parse_value("eb", parts[2])
        else:
            raise SpecError(f"unknown error-bound mode {mode!r}; one of "
                            f"{', '.join(_EB_MODES)} or 'psnr'")
        allowed = {f.name for f in dataclasses.fields(cls)}
        for tok in parts[3:]:
            if "=" not in tok:
                raise SpecError(f"expected key=value, got {tok!r} (full spec: {spec!r})")
            key, _, raw = tok.partition("=")
            key = key.strip()
            if key not in allowed:
                raise SpecError(f"unknown spec key {key!r}; allowed: {', '.join(sorted(allowed))}")
            if key in kw:
                raise SpecError(f"duplicate spec key {key!r} in {spec!r}")
            kw[key] = _spec_parse_value(key, raw)
        try:
            return cls(**kw)
        except SpecError:
            raise
        except (ValueError, TypeError) as e:
            raise SpecError(f"invalid compression spec {spec!r}: {e}") from e

    def to_string(self) -> str:
        """Canonical spec string: ``from_string(spec.to_string()) == spec``
        for every valid spec. Non-default fields append as sorted
        ``key=value`` pairs after the ``lossy,<mode>,<value>`` head."""
        defaults = {f.name: f.default for f in dataclasses.fields(self)}
        if (self.psnr_target is not None and self.eb == defaults["eb"]
                and self.eb_mode == defaults["eb_mode"]):
            head = f"lossy,psnr,{_spec_format_value('psnr_target', self.psnr_target)}"
            skip = {"eb", "eb_mode", "psnr_target"}
        else:
            head = f"lossy,{self.eb_mode},{_spec_format_value('eb', self.eb)}"
            skip = {"eb", "eb_mode"}
        pairs = []
        for name in sorted(defaults):
            if name in skip:
                continue
            value = getattr(self, name)
            if value == defaults[name] or value is None:
                continue
            pairs.append(f"{name}={_spec_format_value(name, value)}")
        return ",".join([head] + pairs)


def _sections_pack(header: dict, sections: list[bytes]) -> bytes:
    """Container v2: binary header + u32/u64 section table."""
    hb = pack_obj(header)
    out = bytearray(MAGIC)
    out += struct.pack("<I", len(hb))
    out += hb
    out += struct.pack("<I", len(sections))
    for s in sections:
        out += struct.pack("<Q", len(s))
    for s in sections:
        out += s
    return bytes(out)


def _sections_pack_v1(header: dict, sections: list[bytes]) -> bytes:
    """Legacy container writer (JSON header), kept for compat tests/tools."""
    header = dict(header, _sizes=[len(s) for s in sections])
    hj = json.dumps(header).encode()
    return MAGIC_V1 + len(hj).to_bytes(8, "little") + hj + b"".join(sections)


def _sections_unpack(buf: bytes):
    if buf[: len(MAGIC)] == MAGIC:  # v2: binary header + section table
        off = len(MAGIC)
        (hlen,) = struct.unpack_from("<I", buf, off)
        off += 4
        header = unpack_obj(buf[off : off + hlen])
        off += hlen
        (nsec,) = struct.unpack_from("<I", buf, off)
        off += 4
        sizes = struct.unpack_from(f"<{nsec}Q", buf, off)
        off += 8 * nsec
        sections = []
        for sz in sizes:
            sections.append(buf[off : off + sz])
            off += sz
        return header, sections
    if buf[: len(MAGIC_V1)] == MAGIC_V1:  # v1: JSON header, sizes inline
        off = len(MAGIC_V1)
        hlen = int.from_bytes(buf[off : off + 8], "little")
        off += 8
        header = json.loads(bytes(buf[off : off + hlen]))
        off += hlen
        sections = []
        for sz in header["_sizes"]:
            sections.append(buf[off : off + sz])
            off += sz
        return header, sections
    raise ValueError(f"bad container magic {bytes(buf[:6])!r}; expected {MAGIC!r} or {MAGIC_V1!r}")


@functools.partial(jax.jit, static_argnames=("batch", "padded", "stride", "steps"))
def _reconstruct_device(seq, anc, ovflat, twoeb, restore_ix, anchor_ix, scatter_ix, *,
                        batch, padded, stride, steps):
    """The device decode tail as one program: restore the code grid,
    place anchors and outliers, gather blocks, replay the predictor,
    scatter back. One jit lets XLA plan (and free) the ~10 field-sized
    intermediates; the cached gather indices come in as arguments, not
    as constants baked into the program."""
    cgrid = restore_codes_batch_device(seq, restore_ix, batch, padded, fill=128)
    agrid = blk.place_anchors_batch_jnp(padded, anc, anchor_ix)
    gather = lambda g: blk.gather_blocks_batch_jnp(g, blk.ANCHOR_STRIDE)
    recon_b = decompress_blocks(gather(cgrid), gather(agrid), gather(ovflat.reshape((batch,) + padded)),
                                twoeb, steps, stride)
    return blk.scatter_blocks_batch_jnp(recon_b, scatter_ix, batch, padded)


class _PerCallState(threading.local):
    """Per-thread observability slots of a (possibly shared) Compressor.

    One Compressor may serve many threads at once (the compressd worker
    pool, shard_decompress's frame decoders): every per-call record —
    telemetry, damage report, winning plan, the multi-chunk hold flag —
    lives here so concurrent calls never see each other's state. The
    public ``last_*`` attributes are compatibility views over this
    storage: same-thread call-then-read behaves exactly as before.
    """

    telemetry = None
    damage = None
    plan = None
    hold = False


class Compressor:
    def __init__(self, spec: CompressorSpec | None = None, *, plan_cache=None, **kw):
        self.spec = spec or CompressorSpec(**kw)
        # Optional repro.core.plancache.PlanCache (shareable across
        # compressors and threads): memoizes the tuning outcome per field
        # signature so recurring shapes skip re-autotuning. None (the
        # default) = tune every call, the historical behavior.
        self.plan_cache = plan_cache
        # Per-call observability, stored per-*thread* (see _PerCallState):
        #   last_plan — the winning PredictorPlan of the last predictor=
        #     "auto" compress() on this thread (observability only; the
        #     container header records everything decode needs).
        #   last_telemetry — reset by compress() and decompress(); records
        #     the requested backend/engine, the plan-cache outcome
        #     ("plan_cache": "hit"/"miss") and the chosen pipeline.
        #     decompress() additionally records a "decode" dict (engine,
        #     out, seconds, bytes, mbps). Both put the call's spans and
        #     counters (repro.core.spans.Record) under "trace".
        #   last_damage — reset by decompress(); under on_error="skip"/
        #     "fill" records the DamageReport and the per-chunk intact
        #     mask of a salvaged v3 container (None = fully intact).
        self._call = _PerCallState()

    # ---- compatibility views over the per-thread call state: a thread
    # reads exactly what its own calls recorded, never a concurrent one's
    @property
    def last_plan(self):
        return self._call.plan

    @last_plan.setter
    def last_plan(self, value):
        self._call.plan = value

    @property
    def last_telemetry(self):
        return self._call.telemetry

    @last_telemetry.setter
    def last_telemetry(self, value):
        self._call.telemetry = value

    @property
    def last_damage(self):
        return self._call.damage

    @last_damage.setter
    def last_damage(self, value):
        self._call.damage = value

    @property
    def _telemetry_hold(self):
        return self._call.hold

    @_telemetry_hold.setter
    def _telemetry_hold(self, value):
        self._call.hold = bool(value)

    def _telemetry(self) -> dict:
        if self.last_telemetry is None:
            self.last_telemetry = {"backend": self.spec.backend, "engine": self.spec.engine}
        return self.last_telemetry

    # ------------------------------------------------------------------ utils
    def _abs_eb(self, x: np.ndarray) -> float:
        if self.spec.eb_mode == "abs":
            return float(self.spec.eb)
        # range in f64: a float32 max-min of an extreme-range field
        # (|x| near 3e38) overflows to inf and poisons the bound
        rng = (float(np.max(x)) - float(np.min(x))) if x.size else 0.0
        return float(self.spec.eb) * rng

    @staticmethod
    def _spatial_view(x: np.ndarray):
        """Fold >3-D arrays into (batch, spatial<=3)."""
        nd = min(x.ndim, 3)
        spatial = x.shape[x.ndim - nd :]
        batch = int(np.prod(x.shape[: x.ndim - nd], dtype=np.int64)) if x.ndim > nd else 1
        return x.reshape((batch,) + spatial), spatial

    # -------------------------------------------------------------- compress
    def compress(self, x: np.ndarray) -> bytes:
        """Compress ``x`` to a v1/v2 container under the spec's bound.

        Two guarantees ride on top of the raw pipeline:

        * **Non-finite-safe ingest** — NaN/±Inf points (masked ocean
          cells, sensor dropouts, blowups) are detected up front, pulled
          out into a packed bitmap + exact bit patterns, and replaced
          with an inert finite fill before prediction; decode restores
          the original bit patterns exactly. Finite fields pay nothing
          (one ``isfinite`` scan, unchanged bytes). Fields that are
          entirely non-finite short-circuit to a trivial container.
        * **Bound verification** — under ``spec.verify`` ("sample" by
          default) the fresh container is decoded and checked against
          the declared bound; a violation re-encodes at a tightened
          bound (bounded ladder) and raises
          :class:`~repro.core.errors.BoundViolationError` only when
          repair is exhausted. See ``last_telemetry["verify"]``.
        """
        if not self._telemetry_hold:
            self.last_telemetry = None
        self._telemetry()
        with spans.call("compress") as rec:
            with spans.span("compress.ingest"):
                x = np.ascontiguousarray(spans.to_host(x), np.float32)
                if isinstance(rec, spans.Record):
                    spans.count("in_bytes", int(x.nbytes))
                fin = np.isfinite(x)
                finite = bool(fin.all())
            buf = self._compress_finite(x) if finite else self._compress_nonfinite(x, fin)
        if isinstance(rec, spans.Record):
            self._telemetry()["trace"] = rec
        return buf

    def _compress_finite(self, x: np.ndarray) -> bytes:
        """The historical compress body: ``x`` is canonical f32, all-finite."""
        sp = self.spec
        if sp.eb_mode == "pw_rel":
            buf = self._compress_pw_rel(x)
            return self._verify_repair(x, buf, bound=float(sp.eb), rel=True)
        psnr_hdr = {}
        if sp.psnr_target is not None:
            eb_abs = self._psnr_target_eb(x)
            psnr_hdr["psnr_target"] = float(sp.psnr_target)
        else:
            with spans.span("compress.ingest"):
                eb_abs = self._abs_eb(x)
        base_hdr = {
            "shape": list(x.shape),
            "predictor": sp.predictor,
            "eb_abs": eb_abs,
            "anchor_stride": sp.anchor_stride,
            **psnr_hdr,
        }
        if eb_abs == 0.0:  # constant field (or degenerate): store verbatim min
            buf = _sections_pack(dict(base_hdr, mode="const"), [np.float32(x.reshape(-1)[0] if x.size else 0).tobytes()])
            return self._verify_repair(x, buf, bound=0.0, rel=False)
        if sp.predictor in ("interp", "auto"):
            buf = self._compress_interp(x, eb_abs, base_hdr)
        elif sp.predictor == "lorenzo":
            buf = self._compress_lorenzo(x, eb_abs, base_hdr)
        elif sp.predictor == "offset1d":
            buf = self._compress_offset1d(x, eb_abs, base_hdr)
        else:
            raise ValueError(sp.predictor)
        return self._verify_repair(x, buf, bound=eb_abs, rel=False)

    # ---------------------------------------------------- non-finite ingest
    def _compress_nonfinite(self, x: np.ndarray, fin: np.ndarray) -> bytes:
        """Canonicalization pass for fields carrying NaN/±Inf.

        The non-finite points are recorded as ``[packbits(mask),
        zlib(u32 bit patterns)]`` sections of an ``"nfsafe"`` wrapper
        container (mode is the versioned header extension — old readers
        of *finite* containers are untouched, and a finite field never
        pays a byte); the field itself, with non-finite points replaced
        by the median of the finite points, rides the normal path as a
        complete inner container, so plan caching / engines / verify all
        apply. Decode restores the exact original bit patterns (NaN
        payloads included). An entirely non-finite field short-circuits
        to a trivial ``"nonfinite"`` container of just the patterns.
        """
        mask = ~fin
        n_bad = int(np.count_nonzero(mask))
        flat = x.reshape(-1)
        pats = flat.view(np.uint32)[mask.reshape(-1)]
        tel = self._telemetry()
        tel["nonfinite"] = {"n": n_bad, "total": int(x.size)}
        if n_bad == x.size:  # nothing finite to predict from: patterns only
            header = {"shape": list(x.shape), "mode": "nonfinite", "n_nonfinite": n_bad}
            return _sections_pack(header, [zlib.compress(pats.tobytes(), 6)])
        fill = float(np.median(flat[fin.reshape(-1)]))
        xf = x.copy()
        xf[mask] = np.float32(fill)
        ibuf = self._compress_finite(xf)
        header = {"shape": list(x.shape), "mode": "nfsafe", "n_nonfinite": n_bad,
                  "fill": fill}
        return _sections_pack(header, [ibuf, np.packbits(mask.reshape(-1)).tobytes(),
                                       zlib.compress(pats.tobytes(), 6)])

    def _decompress_nonfinite(self, header, sections, shape) -> np.ndarray:
        pats = np.frombuffer(zlib.decompress(sections[0]), np.uint32)
        return pats.copy().view(np.float32).reshape(shape)

    def _decompress_nfsafe(self, header, sections, shape, device: bool = False) -> np.ndarray:
        ihdr, isec = _sections_unpack(sections[0])
        y = spans.to_host(self._decompress_sections(ihdr, isec, device=device))
        flat = y.reshape(-1).astype(np.float32).copy()
        mask = np.unpackbits(np.frombuffer(sections[1], np.uint8), count=flat.size).astype(bool)
        pats = np.frombuffer(zlib.decompress(sections[2]), np.uint32)
        flat.view(np.uint32)[mask] = pats  # exact bit patterns, NaN payloads included
        return flat.reshape(shape)

    # ------------------------------------------------ bound verification
    def _verify_check(self, x: np.ndarray, buf: bytes, *, bound: float, rel: bool):
        """Decode ``buf`` and measure the worst error vs the all-finite
        ``x``: absolute error, or point-wise relative error (``rel=True``,
        zeros must reconstruct as zeros). Sample mode checks a
        deterministic ≤``_VERIFY_SAMPLE``-point stride sample. Returns
        ``(max_err, n_checked)``."""
        hold, self._telemetry_hold = self._telemetry_hold, True
        try:
            y = self.decompress(buf)
        finally:
            self._telemetry_hold = hold
        xf = x.reshape(-1).astype(np.float64)
        yf = np.asarray(y, np.float64).reshape(-1)
        if self.spec.verify == "sample" and xf.size > _VERIFY_SAMPLE:
            idx = np.linspace(0, xf.size - 1, _VERIFY_SAMPLE).astype(np.int64)
            xf, yf = xf[idx], yf[idx]
        if not xf.size:
            return 0.0, 0
        if rel:
            nz = xf != 0.0
            err = float(np.max(np.abs(yf[nz] - xf[nz]) / np.abs(xf[nz]))) if nz.any() else 0.0
            if np.any(yf[~nz] != 0.0):  # exact-zero contract of pw_rel
                err = float("inf")
            return err, int(xf.size)
        return float(np.max(np.abs(yf - xf))), int(xf.size)

    def _repair_encode(self, x: np.ndarray, eb_new: float, rel: bool) -> bytes:
        """One rung of the repair ladder: re-encode at a tightened bound.

        Abs-domain repairs pin ``eb_mode="abs"`` (the tightened value IS
        the new absolute bound, whatever mode derived the original);
        pw_rel repairs tighten the relative bound. The inner compressor
        runs ``verify="off"`` — the ladder re-verifies against the
        *original* bound itself."""
        sp = self.spec
        if rel:
            rspec = dataclasses.replace(sp, eb=float(eb_new), verify="off")
        else:
            rspec = dataclasses.replace(sp, eb_mode="abs", eb=float(eb_new),
                                        psnr_target=None, verify="off")
        return Compressor(rspec, plan_cache=self.plan_cache).compress(x)

    def _verify_repair(self, x: np.ndarray, buf: bytes, *, bound: float, rel: bool) -> bytes:
        """Post-encode bound enforcement (``spec.verify`` != "off").

        Decode-and-check the fresh container; on violation re-encode at a
        halved bound, re-verify against the ORIGINAL bound, up to
        ``_REPAIR_POLICY.attempts`` rungs, then raise
        :class:`BoundViolationError`. The outcome — mode, points checked,
        worst error, bound, repair count — lands in
        ``last_telemetry["verify"]`` either way."""
        sp = self.spec
        if sp.verify == "off":
            return buf
        tel = self._telemetry()
        with spans.span("compress.verify"):
            max_err, checked = self._verify_check(x, buf, bound=bound, rel=rel)
        repairs = 0
        cur = float(bound)
        limit = bound * (1.0 + _VERIFY_SLACK) + 1e-12  # f32 rounding headroom
        while not max_err <= limit:  # NaN (a non-finite decode) is a violation too
            if repairs >= _REPAIR_POLICY.attempts or cur <= 0.0:
                tel["verify"] = {"mode": sp.verify, "checked": checked,
                                 "max_err": max_err, "bound": bound, "repairs": repairs}
                raise BoundViolationError(
                    f"bound violation survived {repairs} repair(s): max err "
                    f"{max_err:.6g} > declared bound {bound:.6g} "
                    f"(verify={sp.verify!r}, {checked} points checked)",
                    max_err=max_err, bound=bound, repairs=repairs)
            repairs += 1
            cur *= _REPAIR_TIGHTEN
            try:
                buf = self._repair_encode(x, cur, rel)
            except ValueError as e:  # tightened bound fell off the codec's range
                tel["verify"] = {"mode": sp.verify, "checked": checked,
                                 "max_err": max_err, "bound": bound, "repairs": repairs}
                raise BoundViolationError(
                    f"bound violation (max err {max_err:.6g} > {bound:.6g}) and repair "
                    f"rung {repairs} cannot encode at eb={cur:.6g}: {e}",
                    max_err=max_err, bound=bound, repairs=repairs) from e
            with spans.span("compress.verify"):
                max_err, checked = self._verify_check(x, buf, bound=bound, rel=rel)
        tel["verify"] = {"mode": sp.verify, "checked": checked, "max_err": max_err,
                         "bound": bound, "repairs": repairs}
        return buf

    def _encode_codes(self, seq, pipeline_override: str | None = None) -> tuple[bytes, dict]:
        """Lossless-encode the code stream; returns (payload, header fields).

        ``pipeline="auto"`` routes through the orchestrator: the chosen
        pipeline plus the sampled statistics land in the container header
        (per field), so the selection is recorded, reproducible, and never
        re-inferred at decode time. ``pipeline_override`` (a plan-cache
        hit replaying the pipeline the orchestrator chose for this field
        signature) short-circuits the sampling/scoring pass and encodes
        with the recorded pipeline directly; the header carries
        ``pcached=True`` instead of the orchestrator's ``pchoice`` record.

        Engine dispatch: ``spec.engine`` decides whether ``seq`` is encoded
        by the numpy reference stages or the device engine
        (repro.core.lossless.engine); ``"auto"`` keeps whatever residency
        the stream already has. Either way the payload bytes are identical
        (the engine's bit-identity contract), so the header carries no
        engine field and decode never knows. An engine failure raises.
        """
        sp = self.spec
        is_dev = pipelines._is_jax(seq)
        if sp.engine == "device" and not is_dev:
            seq = spans.to_device(np.ascontiguousarray(seq, np.uint8))
        elif sp.engine == "numpy" and is_dev:
            seq = spans.to_host(seq)
        fixed = sp.pipeline if sp.pipeline != "auto" else pipeline_override
        if fixed is not None:
            hdr = {"pipeline": fixed}
            if sp.pipeline == "auto":
                hdr["pcached"] = True  # plan-cache replay, not a spec-fixed pipeline
            self._telemetry()["pipeline"] = fixed
            return pipelines.encode(seq, fixed), hdr
        histogram = None
        if sp.backend == "pallas" and not pipelines._is_jax(seq):
            import jax

            from repro.kernels.histogram import histogram256_pallas

            interpret = jax.devices()[0].platform != "tpu"
            histogram = lambda d: histogram256_pallas(d, interpret=interpret)  # noqa: E731
        payload, record = orchestrate.encode_auto(
            seq, candidates=sp.pipeline_candidates, histogram=histogram
        )
        self._telemetry()["pipeline"] = record["pipeline"]
        return payload, {"pipeline": record["pipeline"], "pchoice": record}

    @staticmethod
    def inspect(buf: bytes) -> dict:
        """Container header + section sizes, without decompressing.

        Plan-driven containers (``predictor="auto"``) additionally expose
        the winning :class:`~repro.core.autotune.PredictorPlan` under
        ``pplan`` — assembled from the serialized header fields, which is
        why a plan costs the container nothing over a fixed spec.

        v3 (chunked) containers return the global header plus a ``frames``
        list with each frame's inspect dict and byte size, a per-frame
        ``frame_crc_ok`` mask, and — for damaged streams — a ``damage``
        :class:`~repro.core.errors.DamageReport` (inspect never raises for
        frame-level damage; it is the damage-assessment tool).
        """
        if frames_mod.is_v3(buf):
            try:
                header, table = frames_mod.frame_table(buf)
            except ContainerError:
                # structurally damaged stream: report what a salvage pass
                # would recover instead of refusing to look at it
                header = frames_mod.read_header(buf)
                good, report = frames_mod.scan_frames(buf)
                out = dict(header, n_frames=len(good), frame_bytes=[len(p) for _, p in good],
                           frame_indices=[i for i, _ in good], damage=report)
                if header.get("kind") == "chunks":
                    out["frames"] = [Compressor.inspect(p) for _, p in good]
                return out
            crc_ok, payloads = [], []
            for t in table:
                try:
                    payloads.append(frames_mod.read_frame(buf, t))
                    crc_ok.append(True)
                except FrameCRCError:
                    payloads.append(None)
                    crc_ok.append(False)
            out = dict(header, n_frames=len(table), frame_bytes=[size for _, size, _ in table],
                       frame_crc_ok=crc_ok)
            if not all(crc_ok):
                report = DamageReport(declared_frames=len(table), frames_ok=sum(crc_ok),
                                      frames_damaged=len(table) - sum(crc_ok))
                for i, ok in enumerate(crc_ok):
                    if not ok:
                        report.add("crc", table[i][0], index=i, detail="payload CRC32 mismatch")
                out["damage"] = report
            if header.get("kind") == "chunks":  # frames are themselves containers
                out["frames"] = [None if p is None else Compressor.inspect(p) for p in payloads]
            return out
        header, sections = _sections_unpack(buf)
        out = dict(header, section_bytes=[len(s) for s in sections])
        # wrapper modes: section 0 is a full inner container
        if header.get("mode") in ("pw_rel", "nfsafe"):
            out["inner"] = Compressor.inspect(bytes(sections[0]))
        if header.get("mode") == "interp" and header.get("predictor") == "auto" and "splines" in header:
            out["pplan"] = {
                "ndim": len(header["padded"]),
                "anchor_stride": int(header["anchor_stride"]),
                "splines": list(header["splines"]),
                "schemes": list(header["schemes"]),
            }
        return out

    def _run_predictor(self, blocks: np.ndarray, eb_abs: float, steps, stride: int, ndim: int):
        """Dispatch the fused predict+quantize over the whole block batch.

        Returns backend-native arrays (device for the jax backend) — the
        host path converts, the device-engine path keeps them resident.
        """
        if self.spec.backend == "pallas" and ndim == 3:
            from repro.kernels.interp3d import compress_blocks_pallas

            codes_b, outl_b, _ = compress_blocks_pallas(blocks, 2.0 * eb_abs, steps, stride)
            return self._maybe_fault_codes(codes_b), outl_b
        codes_b, outl_b, _ = compress_blocks(spans.to_device(blocks), *quant_steps(eb_abs), steps, stride)
        return self._maybe_fault_codes(codes_b), outl_b

    @staticmethod
    def _maybe_fault_codes(codes_b):
        """Apply the chaos-suite code-perturbation hook (module-level
        ``_CODE_FAULT``, armed by repro.testing.faults.perturb_quant_codes)
        to the fresh quantization codes. The hook must preserve the
        code==0 <=> outlier invariant; it never fires in production."""
        if _CODE_FAULT is None:
            return codes_b
        return _CODE_FAULT(spans.to_host(codes_b))

    def _tune_interp(self, blocks: np.ndarray, eb_abs: float, batch: int, padded_shapes,
                     presampled_of: int | None = None):
        """Resolve the (stride, splines, schemes) the predictor will run.

        ``blocks`` is the full block batch, or — for device-parallel callers
        (repro.core.distributed) that only pulled the tuning sample to host —
        the pre-gathered sample with ``presampled_of`` the true block count.
        Records ``self.last_plan`` under ``predictor="auto"``.
        """
        sp = self.spec
        if sp.predictor == "auto":
            plan = autotune_plan(blocks, 2.0 * eb_abs, tuple(sp.plan_anchor_strides),
                                 field_shape=(batch,) + tuple(padded_shapes),
                                 trial_pipeline=sp.pipeline if sp.pipeline != "auto" else "cr",
                                 reorder=sp.reorder, presampled_of=presampled_of)
            self.last_plan = plan
            return plan.anchor_stride, plan.splines, plan.schemes
        stride, levels = sp.anchor_stride, sp.levels
        if sp.autotune:
            splines, schemes = autotune(blocks, 2.0 * eb_abs, levels, stride,
                                        presampled=presampled_of is not None)
        else:
            splines, schemes = tuple(sp.splines[: len(levels)]), tuple(sp.schemes[: len(levels)])
        return stride, splines, schemes

    def _pack_interp(self, base_hdr: dict, *, cgrid: np.ndarray, anc: np.ndarray,
                     oi: np.ndarray, ov: np.ndarray, stride: int, splines, schemes,
                     pipeline_override: str | None = None) -> bytes:
        """Assemble the interp container from the post-predictor artifacts.

        Shared tail of the host path and the shard_map path
        (repro.core.distributed): identical inputs produce identical bytes,
        which is what makes a v3 frame bit-equal to an independent
        ``compress()`` of the same shard. ``cgrid`` may be a device array —
        the level reorder then runs as a device gather and the code stream
        flows into the encoding engine without ever visiting host.
        """
        sp = self.spec
        with spans.span("compress.reorder"):
            if pipelines._is_jax(cgrid):
                from .reorder import reorder_codes_batch_device

                seq = reorder_codes_batch_device(cgrid, stride, sp.reorder)
            else:
                seq = reorder_codes_batch(cgrid, stride, sp.reorder)
        with spans.span("compress.encode"):
            payload, penc = self._encode_codes(seq, pipeline_override=pipeline_override)
        with spans.span("compress.pack"):
            header = dict(
                base_hdr,
                mode="interp",
                anchor_stride=int(stride),  # may differ from the spec under a plan
                padded=list(cgrid.shape[1:]),
                batch=int(cgrid.shape[0]),
                splines=list(splines),
                schemes=list(schemes),
                reorder=bool(sp.reorder),
                n_outliers=int(oi.size),
                arith=ARITH,
                **penc,
            )
            # No separate plan blob: the plan IS (anchor_stride, splines, schemes),
            # already serialized above — zero container overhead vs a fixed spec.
            # Compressor.inspect reassembles the "pplan" view from those fields;
            # the full diagnostics (scores, candidates) stay on self.last_plan.
            anc = anc.astype(np.float32, copy=False)
            return _sections_pack(header, [payload, anc.tobytes(),
                                           oi.astype(np.int64, copy=False).tobytes(),
                                           ov.astype(np.float32, copy=False).tobytes()])

    def _plan_cache_key(self, x: np.ndarray):
        """Plan-cache signature of this field under this spec, or ``None``
        when the call has nothing cacheable (no cache attached, or a fixed
        spec that neither tunes the predictor nor picks a pipeline).

        The key folds in every spec knob that steers the tuners, so one
        cache can safely serve compressors with different specs.
        """
        sp = self.spec
        if self.plan_cache is None or sp.predictor not in ("interp", "auto"):
            return None
        if not (sp.predictor == "auto" or sp.autotune or sp.pipeline == "auto"):
            return None
        extra = (sp.predictor, int(sp.anchor_stride), tuple(sp.plan_anchor_strides),
                 bool(sp.autotune), bool(sp.reorder), sp.pipeline,
                 tuple(sp.pipeline_candidates or ()), sp.psnr_target)
        return plan_signature(x.shape, x.dtype, sp.eb, sp.eb_mode, stats_bucket(x), extra=extra)

    def _compress_interp(self, x: np.ndarray, eb_abs: float, base_hdr: dict) -> bytes:
        sp = self.spec
        with spans.span("compress.prep"):
            xb, spatial = self._spatial_view(x)
            ndim = len(spatial)
            batch = xb.shape[0]
            padded = blk.pad_field_batch(xb, blk.ANCHOR_STRIDE)
            padded_shapes = padded.shape[1:]
            blocks = blk.gather_blocks_batch(padded, blk.ANCHOR_STRIDE)
            # plan cache: a recurring field signature replays the recorded
            # tuning outcome — predictor plan AND (pipeline="auto") the
            # orchestrator's pipeline choice — skipping both tuners entirely
            ckey = self._plan_cache_key(x)
            cached = self.plan_cache.get(ckey) if ckey is not None else None
        pipe_override = None
        if cached is not None:
            self._telemetry()["plan_cache"] = "hit"
            stride = int(cached["stride"])
            splines, schemes = tuple(cached["splines"]), tuple(cached["schemes"])
            if sp.predictor == "auto" and cached.get("plan") is not None:
                self.last_plan = PredictorPlan.from_header(cached["plan"])
            pipe_override = cached.get("pipeline")
        else:
            if ckey is not None:
                self._telemetry()["plan_cache"] = "miss"
            with spans.span("compress.tune"):
                stride, splines, schemes = self._tune_interp(blocks, eb_abs, batch, padded_shapes)
        with spans.span("compress.predict"):
            steps = build_steps(ndim, blk.BLOCK, levels_for_stride(stride), splines, schemes)
            codes_b, outl_b = self._run_predictor(blocks, eb_abs, steps, stride, ndim)
        with spans.span("compress.scatter"):
            anc = blk.anchor_grid_batch(padded, stride)
            if sp.engine == "device":
                # fused tail: codes stay device-resident through block scatter,
                # level reorder, and the encoding engine (inside _pack_interp);
                # outliers come from the code==0 <=> outlier invariant the
                # sharded path already relies on — no outlier grid crosses over
                cgrid = blk.scatter_blocks_batch_jnp(spans.to_device(codes_b), blk._scatter_index(padded_shapes),
                                                     batch, padded_shapes)
                oi = spans.to_host(jnp.flatnonzero(cgrid.reshape(-1) == 0)).astype(np.int64)
            else:
                codes_b, outl_b = spans.to_host(codes_b), spans.to_host(outl_b)
                cgrid = blk.scatter_blocks_batch(codes_b, batch, padded_shapes, blk.ANCHOR_STRIDE)
                ogrid = blk.scatter_blocks_batch(outl_b, batch, padded_shapes, blk.ANCHOR_STRIDE)
                oi = np.flatnonzero(ogrid.reshape(-1)).astype(np.int64)  # already batch-global
            ov = padded.reshape(-1)[oi]
        buf = self._pack_interp(base_hdr, cgrid=cgrid, anc=anc, oi=oi, ov=ov,
                                stride=stride, splines=splines, schemes=schemes,
                                pipeline_override=pipe_override)
        if ckey is not None and cached is None:
            plan = self.last_plan if sp.predictor == "auto" else None
            self.plan_cache.put(ckey, {
                "stride": int(stride), "splines": tuple(splines), "schemes": tuple(schemes),
                "plan": None if plan is None else plan.to_header(),
                # pipeline recorded only when the orchestrator chose it —
                # a fixed pipeline needs no replay
                "pipeline": self._telemetry().get("pipeline") if sp.pipeline == "auto" else None,
            })
        return buf

    def _compress_lorenzo(self, x: np.ndarray, eb_abs: float, base_hdr: dict) -> bytes:
        xb, spatial = self._spatial_view(x)
        twoeb = jnp.float32(2.0 * eb_abs)
        codes, outl, cfull, _ = lor.lorenzo_encode(spans.to_device(xb), twoeb, len(spatial))
        codes, outl, cfull = spans.to_host(codes), spans.to_host(outl), spans.to_host(cfull)
        fi = np.flatnonzero(outl.reshape(-1))
        with spans.span("compress.encode"):
            payload, penc = self._encode_codes(codes.reshape(-1))
        header = dict(base_hdr, mode="lorenzo", batch=int(xb.shape[0]), spatial=list(spatial), n_outliers=int(fi.size), **penc)
        return _sections_pack(header, [payload, fi.astype(np.int64).tobytes(), cfull.reshape(-1)[fi].astype(np.int32).tobytes()])

    def _compress_offset1d(self, x: np.ndarray, eb_abs: float, base_hdr: dict) -> bytes:
        twoeb = jnp.float32(2.0 * eb_abs)
        codes = spans.to_host(lor.offset1d_encode(spans.to_device(x), twoeb))
        payload, hdr = fl_encode(codes)
        header = dict(base_hdr, mode="offset1d", fl=hdr)
        return _sections_pack(header, [payload])

    # ------------------------------------------------------------- pw_rel
    def _compress_pw_rel(self, x: np.ndarray) -> bytes:
        """Point-wise-relative bound (SZ3's ``pw_rel``) via the log-domain
        transform: compress ``y = ln|x|`` under an absolute bound
        ``eb_log < log1p(eb)``, so every nonzero point satisfies
        ``|x'/x - 1| = |exp(y' - y) - 1| <= eb``; signs and exact zeros
        ride packed bitmaps and reconstruct exactly. ``y`` takes the
        existing quantize -> orchestrate -> engine path unchanged (the
        inner payload is a complete v2 container), so plan caching
        and engine selection apply. The margin
        subtracted from ``log1p(eb)`` covers the float32 storage of the
        log field and the f64->f32 rounding of the reconstruction, making
        the bound hold in delivered float32 arithmetic, not just in exact
        math."""
        sp = self.spec
        eb = float(sp.eb)
        flat = x.reshape(-1)
        zero = flat == 0.0
        nz = ~zero
        # sign over ALL points (not just nonzero): -0.0 compares equal to
        # 0.0 and rides the zero bitmap, so its signbit must be recorded
        # here for the decode side to restore -0.0 bit-exactly
        sign = np.signbit(flat)
        y64 = np.log(np.abs(flat[nz].astype(np.float64)))
        y32 = y64.astype(np.float32)
        cast_err = float(np.max(np.abs(y64 - y32))) if y32.size else 0.0
        slack = 1.2e-7  # f64->f32 rounding of exp(y') on the way back out
        eb_log = (float(np.log1p(eb)) - cast_err - slack) * (1.0 - 2e-4)
        if eb_log <= 0:
            worst = float(np.abs(flat[nz].astype(np.float64))[np.argmax(np.abs(y64 - y32))])
            raise ValueError(
                f"eb={eb:g} is below the float32 pw_rel transform's resolution at "
                f"|x|={worst:.6g} (log-domain cast error {cast_err:.3g} eats the "
                f"whole log1p(eb) budget); use a larger bound or eb_mode='abs'")
        fill = float(y32.min()) if y32.size else 0.0  # zero slots: inert filler
        y = np.full(flat.shape, np.float32(fill), np.float32)
        y[nz] = y32
        inner = Compressor(dataclasses.replace(sp, eb_mode="abs", eb=eb_log, verify="off"),
                           plan_cache=self.plan_cache)
        ibuf = inner.compress(y.reshape(x.shape))
        itel = inner.last_telemetry or {}
        tel = self._telemetry()
        for k in ("pipeline", "plan_cache"):
            if k in itel:
                tel[k] = itel[k]
        self.last_plan = inner.last_plan
        header = {"shape": list(x.shape), "mode": "pw_rel", "predictor": sp.predictor,
                  "eb_rel": eb, "eb_abs": float(eb_log), "n_zero": int(zero.sum())}
        return _sections_pack(header, [ibuf, np.packbits(sign).tobytes(),
                                       np.packbits(zero).tobytes()])

    def _decompress_pw_rel(self, header, sections, shape, device: bool = False) -> np.ndarray:
        ihdr, isec = _sections_unpack(sections[0])
        y = spans.to_host(self._decompress_sections(ihdr, isec, device=device))
        sign = np.unpackbits(np.frombuffer(sections[1], np.uint8), count=y.size).astype(bool)
        zero = np.unpackbits(np.frombuffer(sections[2], np.uint8), count=y.size).astype(bool)
        out = np.exp(y.reshape(-1).astype(np.float64))
        # zero first, negate second: a signed zero slot (new containers
        # record signbit over all points) becomes -0.0 bit-exactly; old
        # containers never mark a zero slot in `sign`, so the order swap
        # decodes them identically to before
        out[zero] = 0.0
        out[sign] = -out[sign]
        return out.astype(np.float32).reshape(shape)

    # -------------------------------------------------------- psnr target
    def _psnr_trial_field(self, x: np.ndarray) -> np.ndarray:
        """The trial sample the eb search compresses: the field itself when
        small, else a centered <=64-wide crop per axis (a crop keeps the
        field's smoothness structure; a strided subsample would not)."""
        if x.size <= (1 << 20):
            return x
        sl = []
        for d in x.shape:
            if d <= 64:
                sl.append(slice(None))
            else:
                c = d // 2
                sl.append(slice(c - 32, c + 32))
        return np.ascontiguousarray(x[tuple(sl)])

    def _psnr_target_eb(self, x: np.ndarray) -> float:
        """Binary-search the absolute eb whose reconstruction lands on
        ``spec.psnr_target`` dB (range-normalized, full-field range).

        The search runs on MSE, not PSNR — ``mse_target = rng^2 *
        10^(-target/10)`` — so the trial crop's narrower value range
        cannot skew the dB arithmetic, and aims 0.5 dB above target so
        trial-vs-full sampling error stays inside a ±1 dB window. Each
        trial compresses with the cheap fixed configuration: distortion
        is independent of the lossless pipeline (it is lossless) and
        nearly independent of predictor tuning (quantization error is
        ~uniform within ±eb), so the trials skip both tuners."""
        sp = self.spec
        target = float(sp.psnr_target)
        rng = (float(np.max(x)) - float(np.min(x))) if x.size else 0.0
        if rng == 0.0:
            return 0.0  # constant field: verbatim const container, PSNR = inf
        trial = self._psnr_trial_field(x)
        tspec = dataclasses.replace(
            sp, psnr_target=None, eb_mode="abs", eb=1.0,
            predictor="interp" if sp.predictor == "auto" else sp.predictor,
            pipeline="none", pipeline_candidates=None, autotune=False, verify="off")
        mse_aim = rng * rng * 10.0 ** (-(target + 0.5) / 10.0)
        trials = 0

        def mse_at(eb_abs: float) -> float:
            nonlocal trials
            trials += 1
            comp = Compressor(dataclasses.replace(tspec, eb=float(eb_abs)))
            y = comp.decompress(comp.compress(trial))
            d = trial.astype(np.float64) - y.astype(np.float64)
            return float(np.mean(d * d))

        # uniform-quantization model (mse ~ eb^2/3) seeds the bracket
        eb0 = min(float(np.sqrt(3.0 * mse_aim)), 0.25 * rng)
        lo = hi = eb0
        if mse_at(eb0) <= mse_aim:  # feasible: push eb up until it breaks
            grown = False
            for _ in range(8):
                hi = lo * 4.0
                if mse_at(hi) > mse_aim:
                    grown = True
                    break
                lo = hi
            if not grown:
                hi = lo  # even the loosest probe met the target
        else:  # infeasible at the model guess: tighten until it holds
            for _ in range(12):
                lo = lo / 4.0
                if mse_at(lo) <= mse_aim:
                    break
            else:
                raise ValueError(
                    f"psnr_target={target:g} dB unreachable: trial mse "
                    f"{mse_at(lo):.3g} > target {mse_aim:.3g} even at eb={lo:.3g}")
        while hi / lo > 1.02:  # log-bisect, keeping lo on the feasible side
            mid = float(np.sqrt(lo * hi))
            if mse_at(mid) <= mse_aim:
                lo = mid
            else:
                hi = mid
        self._telemetry()["psnr_search"] = {
            "target_db": target, "eb_abs": float(lo), "trials": trials,
            "trial_elems": int(trial.size),
        }
        return float(lo)

    # ------------------------------------------------------------ decompress
    def decompress(self, buf: bytes, frames=None, *, on_error: str = "raise",
                   fill_value: float = 0.0, out: str = "numpy") -> np.ndarray:
        """Decompress a v1/v2/v3 container.

        ``frames``: v3 containers only — an iterable of frame indices to
        decode (any order). The result is the selected chunks concatenated
        along the container's chunk axis in the order given; ``None``
        decodes every frame and reassembles the full field.

        ``out``: ``"numpy"`` (default) returns a host ndarray; ``"device"``
        returns a device-resident ``jax.Array`` — with ``engine="device"``
        (or ``"auto"``, which follows ``out``) the code stream decodes
        through the stages' device twins and stays on device through
        restore/anchor-placement/reconstruction, so the field never
        bounces through host memory. Bytes-for-bytes the result matches
        the numpy path (the engine bit-identity contract); a device decode
        failure raises. Each call also records
        ``last_telemetry["decode"]`` (engine, out, seconds, bytes, MB/s),
        its seconds read from the call's span record (``["trace"]``).

        ``on_error`` — degraded-mode decode of damaged containers:

        * ``"raise"`` (default): any integrity failure raises the typed
          error (:mod:`repro.core.errors`) — the strict historical
          behavior.
        * ``"skip"``: v3 only — damaged chunks are omitted from the
          reassembled field (the result is shorter along the chunk axis).
        * ``"fill"``: damaged chunks are reconstructed as
          ``fill_value`` blocks of the right shape, so the result keeps
          the container's full geometry.

        Either degraded mode records what happened on ``self.last_damage``
        (``None`` when the container was fully intact): a dict with the
        :class:`~repro.core.errors.DamageReport` under ``"report"`` and
        the per-requested-chunk intact mask under ``"chunks_ok"``.
        """
        if on_error not in ("raise", "skip", "fill"):
            raise ValueError(f"on_error must be 'raise', 'skip' or 'fill', got {on_error!r}")
        if out not in ("numpy", "device"):
            raise ValueError(f"out must be 'numpy' or 'device', got {out!r}")
        hold = self._telemetry_hold
        if not hold:
            self.last_telemetry = None
        tel = self._telemetry()
        want_dev = self.spec.engine == "device" or (self.spec.engine == "auto" and out == "device")
        self.last_damage = None
        with spans.call("decompress") as rec:
            if isinstance(rec, spans.Record):
                spans.count("in_bytes", len(buf))
            if frames_mod.is_v3(buf):
                result = self._decompress_v3(buf, frames, on_error=on_error,
                                             fill_value=fill_value, out=out)
            else:
                if frames is not None:
                    raise ValueError("frames= is only meaningful for v3 (chunked) containers")
                try:
                    with spans.span("decompress.unpack"):
                        header, sections = _sections_unpack(buf)
                    result = self._decompress_sections(header, sections, device=want_dev)
                except Exception as e:
                    if on_error != "fill":
                        raise
                    # salvage a single container only when its header still tells
                    # us the field geometry; otherwise there is nothing to fill
                    try:
                        header, _ = _sections_unpack(buf)
                        shape = tuple(header["shape"])
                    except Exception:
                        raise e from None
                    report = DamageReport()
                    report.add("decode", 0, index=0, detail=repr(e))
                    report.frames_damaged = 1
                    self.last_damage = {"report": report, "chunks_ok": [False], "on_error": on_error}
                    result = np.full(shape, np.float32(fill_value), np.float32)
            with spans.span("decompress.reconstruct"):
                if out == "device" and isinstance(result, np.ndarray):
                    result = spans.to_device(result)
                elif out == "numpy" and not isinstance(result, np.ndarray):
                    result = spans.to_host(result)
                if not hold and not isinstance(result, np.ndarray):
                    result.block_until_ready()  # honest timing for device results
        if not hold:
            dt = rec.seconds
            tel["decode"] = {
                "engine": "device" if want_dev else "numpy", "out": out,
                "seconds": dt, "bytes": int(result.nbytes),
                "mbps": (result.nbytes / dt / 1e6) if dt > 0 else 0.0,
            }
        if isinstance(rec, spans.Record):
            tel["trace"] = rec
        return result

    def _decompress_sections(self, header, sections, device: bool = False) -> np.ndarray:
        shape = tuple(header["shape"])
        mode = header["mode"]
        if mode == "const":
            v = np.frombuffer(sections[0], np.float32)[0]
            return np.full(shape, v, np.float32)
        if mode == "interp":
            return self._decompress_interp(header, sections, shape, device=device)
        if mode == "lorenzo":
            return self._decompress_lorenzo(header, sections, shape, device=device)
        if mode == "offset1d":
            codes = fl_decode(sections[0], header["fl"])
            out = lor.offset1d_decode(spans.to_device(codes), jnp.float32(2.0 * header["eb_abs"]))
            return out.reshape(shape) if device else spans.to_host(out).reshape(shape)
        if mode == "pw_rel":
            return self._decompress_pw_rel(header, sections, shape, device=device)
        if mode == "nfsafe":
            return self._decompress_nfsafe(header, sections, shape, device=device)
        if mode == "nonfinite":
            return self._decompress_nonfinite(header, sections, shape)
        raise ValueError(mode)

    def _decompress_interp(self, header, sections, shape, device: bool = False) -> np.ndarray:
        with spans.span("decompress.unpack"):
            stride = header["anchor_stride"]
            padded_shapes = tuple(header["padded"])
            batch = header["batch"]
            ndim = len(padded_shapes)
            eb_abs = header["eb_abs"]
            psize = int(np.prod(padded_shapes))
            anc_shape = tuple((d - 1) // stride + 1 for d in padded_shapes)
            levels = levels_for_stride(stride)
            # Containers that predate recorded step tables (or hand-rolled v1
            # headers without them) decode with the default cubic/md hierarchy.
            splines = tuple(header.get("splines", ("cubic",) * len(levels)))
            schemes = tuple(header.get("schemes", ("md",) * len(levels)))
            steps = build_steps(ndim, blk.BLOCK, levels, splines, schemes)
            spatial = shape[len(shape) - ndim :] if len(shape) >= ndim else shape
            sl = (slice(None),) + tuple(slice(0, s) for s in spatial)
            anc = np.frombuffer(sections[1], np.float32)
            oi = np.frombuffer(sections[2], np.int64)
            ov = np.frombuffer(sections[3], np.float32)
            arith = header.get("arith")
            if arith not in (None, ARITH):
                raise ValueError(f"unknown predictor arithmetic {arith!r}; this build replays {ARITH}")
            # arith containers were quantized with quant_steps' step; older ones with 2 * eb_abs
            twoeb = np.float32(2.0 * eb_abs) if arith is None else quant_steps(eb_abs)[0]
        if device and arith is not None:
            # device-resident tail: codes decode through the stage twins and
            # every hop to the reconstructed field is a jnp gather — same
            # bytes as the numpy path below (bit-identity contract).
            # Containers without "arith" take the host path, whose replay
            # must run on XLA:CPU.
            with spans.span("decompress.lossless"):
                seq = pipelines.decode(sections[0], device=True)
            with spans.span("decompress.reconstruct"):
                ovflat = jnp.zeros(batch * psize, jnp.float32)
                if oi.size:  # outlier indices are batch-global and unique
                    ovflat = ovflat.at[spans.to_device(oi)].set(spans.to_device(ov))
                reorder = bool(header.get("reorder", True))
                out = _reconstruct_device(
                    seq, spans.to_device(anc).reshape((batch,) + anc_shape), ovflat, twoeb,
                    _restore_gather(padded_shapes, stride, reorder), blk._anchor_index(padded_shapes, stride),
                    blk._scatter_index(padded_shapes), batch=batch, padded=padded_shapes, stride=stride,
                    steps=steps)
                return out[sl].reshape(shape)
        with spans.span("decompress.lossless"):
            seq = pipelines.decode(sections[0])
        with spans.span("decompress.restore"):
            cgrid = restore_codes_batch(seq, batch, padded_shapes, fill=128, dtype=np.uint8,
                                        stride=stride, reorder=header.get("reorder", True))
            agrid = blk.place_anchors_batch(padded_shapes, anc.reshape((batch,) + anc_shape), stride)
            ovflat = np.zeros(batch * psize, np.float32)
            ovflat[oi] = ov  # outlier indices are batch-global
            ovgrid = ovflat.reshape((batch,) + padded_shapes)
            cb = blk.gather_blocks_batch(cgrid, blk.ANCHOR_STRIDE)
            ab = blk.gather_blocks_batch(agrid, blk.ANCHOR_STRIDE)
            vb = blk.gather_blocks_batch(ovgrid, blk.ANCHOR_STRIDE)
        with spans.span("decompress.reconstruct"):
            if arith is None:
                # written by the matmul-form encoder on XLA:CPU: replayed there,
                # so such archives decode to the same floats on any host
                with jax.default_device(jax.devices("cpu")[0]):
                    recon_b = np.asarray(decompress_blocks_matmul(cb, ab, vb, twoeb, steps, stride))
            else:
                recon_b = spans.to_host(decompress_blocks(spans.to_device(cb), spans.to_device(ab),
                                                          spans.to_device(vb), twoeb, steps, stride))
        with spans.span("decompress.scatter"):
            out = blk.scatter_blocks_batch(recon_b, batch, padded_shapes, blk.ANCHOR_STRIDE)
            return out[sl].reshape(shape)

    @staticmethod
    def _chunk_shape(header: dict, i: int) -> tuple:
        """Chunk ``i``'s field shape from a v3 chunk-stream header."""
        shape = list(header["shape"])
        axis = int(header.get("axis", 0))
        shape[axis] = int(header["chunk_sizes"][i])
        return tuple(shape)

    def _salvage_payloads(self, buf, on_error: str):
        """Per-frame payloads of a v3 stream, degraded-mode aware.

        Returns ``(header, payloads: dict[int, bytes], report)``. Strict
        mode raises on the first integrity failure; degraded modes fall
        back to :func:`repro.core.frames.scan_frames` when the frame walk
        itself is damaged (corrupt lengths, truncation), and mark
        CRC-damaged frames absent otherwise.
        """
        try:
            header, table = frames_mod.frame_table(buf)
        except ContainerError:
            if on_error == "raise":
                raise
            header = frames_mod.read_header(buf)
            good, report = frames_mod.scan_frames(buf)
            return header, dict(good), report
        report = DamageReport(declared_frames=len(table))
        payloads = {}
        for i, t in enumerate(table):
            try:
                payloads[i] = frames_mod.read_frame(buf, t)
                report.frames_ok += 1
            except FrameCRCError:
                if on_error == "raise":
                    raise
                report.add("crc", t[0], index=i, detail="payload CRC32 mismatch")
                report.frames_damaged += 1
        return header, payloads, report

    def _decompress_v3(self, buf: bytes, frames=None, *, on_error: str = "raise",
                       fill_value: float = 0.0, out: str = "numpy") -> np.ndarray:
        """Chunked container v3: decode frames (each a v1/v2 container of one
        chunk) independently and reassemble along the chunk axis. Under
        ``on_error="skip"``/``"fill"`` damaged chunks cost only themselves:
        the other chunks reassemble normally (see :meth:`decompress`).
        ``out="device"`` decodes each frame onto device and concatenates
        there — chunks land in per-shard device buffers without a host
        bounce."""
        header, payloads, report = self._salvage_payloads(buf, on_error)
        if header.get("kind") != "chunks":
            raise ValueError(
                f"v3 container kind {header.get('kind')!r} is not a compressor chunk "
                "stream; use its producer's reader"
            )
        n_chunks = len(header["chunk_sizes"])
        idx = list(range(n_chunks)) if frames is None else [int(i) for i in frames]
        if not idx:
            raise ValueError("frames= selected no frames; pass at least one index (or None for all)")
        parts, mask = [], []
        # per-frame decompress() calls share this call's telemetry dict
        # instead of resetting it frame by frame
        hold, self._telemetry_hold = self._telemetry_hold, True
        try:
            for i in idx:
                part = None
                if i in payloads:
                    if on_error == "raise":
                        part = self.decompress(payloads[i], out=out)
                    else:
                        try:
                            part = self.decompress(payloads[i], out=out)
                        except Exception as e:  # resync false positive / garbage past CRC
                            report.add("decode", -1, index=i, detail=repr(e))
                            report.frames_damaged += 1
                elif on_error == "raise":
                    raise ContainerError(f"frame {i} missing from v3 container")
                mask.append(part is not None)
                if part is not None:
                    parts.append(part)
                elif on_error == "fill":
                    parts.append(np.full(self._chunk_shape(header, i), np.float32(fill_value), np.float32))
        finally:
            self._telemetry_hold = hold
        if not report.ok:
            self.last_damage = {"report": report, "chunks_ok": mask, "on_error": on_error}
        if not parts:
            raise ContainerError(
                f"no decodable frames in damaged v3 container ({report.summary()})"
            )
        axis = int(header.get("axis", 0))
        if len(parts) == 1:
            return parts[0]
        if out == "device":
            return jnp.concatenate([spans.to_device(p) for p in parts], axis=axis)
        return np.concatenate(parts, axis=axis)

    def _decompress_lorenzo(self, header, sections, shape, device: bool = False) -> np.ndarray:
        batch, spatial = header["batch"], tuple(header["spatial"])
        oi = np.frombuffer(sections[1], np.int64)
        ov = np.frombuffer(sections[2], np.int32)
        if device:
            seq = pipelines.decode(sections[0], device=True)
            codes = seq.reshape((batch,) + spatial)
            ofull = jnp.zeros(codes.size, jnp.int32)
            if oi.size:
                ofull = ofull.at[spans.to_device(oi)].set(spans.to_device(ov))
            out = lor.lorenzo_decode(codes, ofull.reshape(codes.shape),
                                     jnp.float32(2.0 * header["eb_abs"]), len(spatial))
            return out.reshape(shape)
        seq = pipelines.decode(sections[0])
        codes = seq.reshape((batch,) + spatial)
        ofull = np.zeros(codes.size, np.int32)
        ofull[oi] = ov
        out = lor.lorenzo_decode(spans.to_device(codes), spans.to_device(ofull.reshape(codes.shape)),
                                 jnp.float32(2.0 * header["eb_abs"]), len(spatial))
        return spans.to_host(out).reshape(shape)


# ------------------------------------------------------------------ presets
def cusz_hi_auto(eb=1e-3, **kw) -> Compressor:
    """Orchestrated mode: per-field best-fit lossless pipeline (§5.2)."""
    return Compressor(CompressorSpec(eb=eb, pipeline="auto", **kw))


def cusz_hi_autoplan(eb=1e-3, **kw) -> Compressor:
    """Fully synergistic mode: plan-driven predictor (per-level spline/scheme/
    stride autotuning, §5.1.3) + per-field best-fit lossless pipeline (§5.2)."""
    return Compressor(CompressorSpec(eb=eb, predictor="auto", pipeline="auto", **kw))


def cusz_hi_cr(eb=1e-3, **kw) -> Compressor:
    return Compressor(CompressorSpec(eb=eb, pipeline="cr", **kw))


def cusz_hi_crz(eb=1e-3, **kw) -> Compressor:
    """Beyond-paper mode: CR pipeline + open-source zstd tail stage."""
    return Compressor(CompressorSpec(eb=eb, pipeline="crz", **kw))


def cusz_hi_tp(eb=1e-3, **kw) -> Compressor:
    return Compressor(CompressorSpec(eb=eb, pipeline="tp", **kw))


def cusz_l(eb=1e-3) -> Compressor:
    """cuSZ-L baseline: Lorenzo + Huffman."""
    return Compressor(CompressorSpec(eb=eb, predictor="lorenzo", pipeline="hf"))


def cusz_i(eb=1e-3) -> Compressor:
    """cuSZ-I baseline: stride-8 anchors, 3 levels, 1D scheme, Huffman only."""
    return Compressor(
        CompressorSpec(eb=eb, predictor="interp", pipeline="hf", anchor_stride=8, autotune=False,
                       splines=("cubic",) * 3, schemes=("1d",) * 3, reorder=False)
    )


def cuszp2_like(eb=1e-3) -> Compressor:
    """cuSZp2-like baseline: 1-D offset prediction + fixed-length encoding."""
    return Compressor(CompressorSpec(eb=eb, predictor="offset1d", pipeline="none"))


def fzgpu_like(eb=1e-3) -> Compressor:
    """FZ-GPU-like baseline: Lorenzo + bitshuffle + de-redundancy."""
    return Compressor(CompressorSpec(eb=eb, predictor="lorenzo", pipeline="fz"))
