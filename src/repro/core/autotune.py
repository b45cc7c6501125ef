"""Data-adaptive interpolation auto-tuning (paper §5.1.3) — the lossy half
of the synergistic orchestration.

Two tuners live here:

* :func:`autotune` — the legacy per-level (spline x scheme) argmin on
  aggregated absolute prediction error, kept for ``CompressorSpec(
  predictor="interp", autotune=True)`` and the ablation benchmarks.
* :func:`autotune_plan` — the full planner behind ``predictor="auto"``.
  It samples anchor blocks, trial-predicts every candidate spline
  (linear / cubic / natural-cubic) x interpolation scheme ("md" vs the
  per-dimension sequential orderings) per level with quantization
  feedback, and scores candidates by the *entropy of the quantized
  residual codes* — computed through
  :func:`repro.core.lossless.orchestrate.stream_stats`, so the lossy and
  lossless tuners share one cost model. It repeats the per-level greedy
  sweep for every candidate anchor stride and emits a
  :class:`PredictorPlan`: the stride, the per-level (spline, scheme)
  choices, and the scored alternatives for observability.

The plan serializes to a plain dict (``to_header`` / ``from_header``)
that rides the binary container v2 header via ``repro.core.serial``;
containers without a plan decode with the default cubic/md steps.

On the GPU the paper balances thread blocks per level; the TPU analogue is
the sample volume itself (each per-level trial is a handful of small
batched stencil passes), kept at the paper's 0.2 % budget — except that small
fields (<= EXHAUSTIVE_BLOCKS blocks) are sampled exhaustively, which makes
the greedy per-level selection exact for the bench-suite fields.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as _blk
from .lossless import orchestrate as orc
from .lossless import pipelines as _pipelines
from .predictor import CENTER, RADIUS, _anchor_mask, fence, fence_zero, predict, quant_steps, quantize_pred
from .reorder import reorder_codes_batch
from .serial import pack_obj, unpack_obj
from .spans import to_device, to_host
from .stencils import SCHEMES, SPLINES, build_steps

SAMPLE_FRACTION = 0.002
MIN_SAMPLE_BLOCKS = 8
EXHAUSTIVE_BLOCKS = 64       # sample everything below this block count
ANCHOR_BITS = 32             # anchors are stored as raw float32
OUTLIER_BITS = 96            # i64 index + f32 value per outlier
DEFAULT_STRIDES = (16, 8)    # candidate anchor strides for predictor="auto"


def levels_for_stride(stride: int) -> tuple[int, ...]:
    lv, s = [], stride // 2
    while s >= 1:
        lv.append(s)
        s //= 2
    return tuple(lv)


def candidate_splines() -> tuple[str, ...]:
    return SPLINES


def candidate_schemes(ndim: int) -> tuple[str, ...]:
    """"md" plus the two extreme sequential orderings (forward / reverse).

    For ndim == 1 every ordering collapses to the same single sweep.
    """
    if ndim <= 1:
        return ("md",)
    fwd = "1d-" + "".join(map(str, range(ndim)))
    rev = "1d-" + "".join(map(str, reversed(range(ndim))))
    return ("md", fwd, rev)


def fixed_step_baselines(nlev: int = 4) -> dict:
    """Uniform fixed-steps configurations (CompressorSpec kwargs) that
    ``predictor="auto"`` must match or beat — the bench's and the CR-floor
    tests' shared baseline grid."""
    return {
        "cubic-md": dict(splines=("cubic",) * nlev, schemes=("md",) * nlev),
        "linear-md": dict(splines=("linear",) * nlev, schemes=("md",) * nlev),
        "cubic-1d": dict(splines=("cubic",) * nlev, schemes=("1d",) * nlev),
        "natural-cubic-md": dict(splines=("natural-cubic",) * nlev, schemes=("md",) * nlev),
    }


# ------------------------------------------------------------------ plan
@dataclasses.dataclass(frozen=True)
class PredictorPlan:
    """Per-field interpolation plan emitted by :func:`autotune_plan`.

    ``splines`` / ``schemes`` hold one entry per level (largest stride
    first, levels derived from ``anchor_stride``). ``est_bits_per_code``
    is the cost-model score of the winning configuration; ``candidates``
    records the per-stride alternatives that lost, for observability.
    """

    ndim: int
    anchor_stride: int
    splines: tuple[str, ...]
    schemes: tuple[str, ...]
    est_bits_per_code: float = 0.0
    sampled_blocks: int = 0
    candidates: tuple = ()  # ((label, est_bits_per_code), ...) per stride

    def __post_init__(self):
        object.__setattr__(self, "splines", tuple(self.splines))
        object.__setattr__(self, "schemes", tuple(self.schemes))
        object.__setattr__(self, "candidates", tuple(tuple(c) for c in self.candidates))
        if len(self.splines) != len(self.levels) or len(self.schemes) != len(self.levels):
            raise ValueError(
                f"plan needs {len(self.levels)} per-level entries for anchor_stride="
                f"{self.anchor_stride}, got splines={self.splines} schemes={self.schemes}"
            )

    @property
    def levels(self) -> tuple[int, ...]:
        return levels_for_stride(self.anchor_stride)

    def __str__(self) -> str:
        """Compact display form, e.g. ``s16:linear/1d-012,cubic/md,...``."""
        return f"s{self.anchor_stride}:" + ",".join(
            f"{sp}/{sc}" for sp, sc in zip(self.splines, self.schemes)
        )

    def steps(self, B: int = 17):
        return build_steps(self.ndim, B, self.levels, self.splines, self.schemes)

    def to_header(self, include_candidates: bool = False) -> dict:
        """Plain-dict form for the binary container v2 header (core.serial).

        The scored-alternatives record is omitted by default: it is
        kilobytes of labels, which would dominate the container for small
        fields. Pass ``include_candidates=True`` for offline reports.
        """
        h = {
            "ndim": int(self.ndim),
            "anchor_stride": int(self.anchor_stride),
            "splines": list(self.splines),
            "schemes": list(self.schemes),
            "est_bits_per_code": float(self.est_bits_per_code),
            "sampled_blocks": int(self.sampled_blocks),
        }
        if include_candidates:
            h["candidates"] = [[str(lbl), float(bits)] for lbl, bits in self.candidates]
        return h

    @classmethod
    def from_header(cls, h: dict) -> "PredictorPlan":
        return cls(
            ndim=int(h["ndim"]),
            anchor_stride=int(h["anchor_stride"]),
            splines=tuple(h["splines"]),
            schemes=tuple(h["schemes"]),
            est_bits_per_code=float(h.get("est_bits_per_code", 0.0)),
            sampled_blocks=int(h.get("sampled_blocks", 0)),
            candidates=tuple((lbl, bits) for lbl, bits in h.get("candidates", ())),
        )

    def to_bytes(self) -> bytes:
        """Compact binary form (repro.core.serial) — the shape a plan-cache
        entry or a service response carries a plan in."""
        return pack_obj(self.to_header())

    @classmethod
    def from_bytes(cls, buf: bytes) -> "PredictorPlan":
        return cls.from_header(unpack_obj(buf))


# ---------------------------------------------------------- plan-cache keys
_SIG_VERSION = "ps1"        # bump when signature semantics change
_STATS_SAMPLE_CAP = 65536   # stats-bucket subsample size (uniform strided)
_STD_BUCKET_QUARTERS = 4    # std bucket resolution: quarter powers of two


def stats_bucket(x: np.ndarray) -> tuple[int, int]:
    """Coarse distribution bucket of a field, for plan-cache keying.

    Two integers: the power-of-two exponent of the value range, and the
    range-normalized standard deviation quantized to quarter powers of
    two. Fields whose tuning outcome would plausibly differ (a 1000x
    larger dynamic range, a flat vs. a noisy field) land in different
    buckets; run-to-run noise on the *same* recurring tensor does not —
    that is the whole point: the millions-of-users case is the same
    shapes with the same statistics arriving forever.

    Cost: one strided subsample (<= ``_STATS_SAMPLE_CAP`` elements) and
    two reductions — microseconds against the planner's trial encodes.
    """
    flat = np.asarray(x).reshape(-1)
    if flat.size == 0:
        return (0, 0)
    if flat.size > _STATS_SAMPLE_CAP:
        flat = flat[:: max(1, flat.size // _STATS_SAMPLE_CAP)]
    lo = float(np.min(flat))
    rng = float(np.max(flat)) - lo
    if not math.isfinite(rng) or rng <= 0.0:
        return (-(1 << 20), 0)  # constant (or non-finite) field: its own bucket
    b_rng = math.frexp(rng)[1]
    rel_std = float(np.std(flat)) / rng
    if rel_std <= 0.0:
        return (b_rng, -(1 << 20))
    return (b_rng, int(round(_STD_BUCKET_QUARTERS * math.log2(rel_std))))


def plan_signature(shape, dtype, eb: float, eb_mode: str, bucket=(), *, extra=()) -> tuple:
    """Hashable plan-cache key: field geometry + error-bound config +
    coarse stats bucket (+ caller extras, e.g. the spec knobs that steer
    the tuner). Two fields share a signature exactly when a cached tuning
    outcome for one is a valid (and near-optimal) plan for the other.
    """
    return (
        _SIG_VERSION,
        tuple(int(s) for s in shape),
        np.dtype(dtype).str,
        float(eb),
        str(eb_mode),
        tuple(bucket),
        tuple(extra),
    )


# ------------------------------------------------------------ trial passes
@functools.partial(jax.jit, static_argnums=(4, 5))
def _level_pass(recon, orig, twoeb, inv2eb, steps, update: bool):
    """Run one level's steps; return (new_recon, sum |orig-pred| over targets).

    Legacy scorer for :func:`autotune` (absolute-error argmin).
    """
    err = jnp.zeros((), jnp.float32)
    z = fence_zero(twoeb)
    for step in steps:
        pred = predict(recon, step, z)
        m = jnp.asarray(step.mask)
        err = err + jnp.sum(jnp.where(m, jnp.abs(orig - pred), 0.0))
        q = jnp.rint((orig - pred) * inv2eb)
        outl = jnp.abs(q) > RADIUS
        rec = jnp.where(outl, orig, pred + fence(q * twoeb, z))
        recon = jnp.where(m, rec, recon)
    return recon, err


@functools.partial(jax.jit, static_argnums=(4,))
def _level_codes_pass(recon, orig, twoeb, inv2eb, steps):
    """One level with quantization feedback, returning what the encoder
    would emit: (new_recon, codes) where ``codes`` carries the uint8
    quantization code at this level's target points and -1 elsewhere.

    Shares predictor.quantize_pred, so the stream the tuner scores is
    bit-identical to the stream the compressor then produces.
    """
    codes = jnp.full(orig.shape, -1, jnp.int32)
    z = fence_zero(twoeb)
    for step in steps:
        pred = predict(recon, step, z)
        code, _, rec = quantize_pred(orig, pred, twoeb, inv2eb, z)
        m = jnp.asarray(step.mask)
        recon = jnp.where(m, rec, recon)
        codes = jnp.where(m, code, codes)
    return recon, codes


def _level_emits(codes_np: np.ndarray) -> np.ndarray:
    """Flatten one level's emitted codes (drop non-target -1 fill) to uint8,
    block-major then row-major — the level-segment order the reorder keeps."""
    flat = codes_np.reshape(-1)
    return flat[flat >= 0].astype(np.uint8)


def _code_bits(hist: np.ndarray, n_outliers: int) -> float:
    """Estimated encoded bits for one level's code stream.

    Shares the lossless orchestrator's cost model: the byte-histogram
    entropy from :func:`orchestrate.stream_stats` (fed through its
    ``histogram`` hook) bounds what any registered entropy-coding pipeline
    achieves; outliers pay their raw storage on top.
    """
    hist = np.asarray(hist, np.int64)
    n = int(hist.sum())
    if n == 0:
        return 0.0
    stats = orc.stream_stats(np.zeros(0, np.uint8), n_total=n, histogram=lambda _: hist)
    return n * stats["entropy"] + int(n_outliers) * OUTLIER_BITS


def plan_sample_indices(nb: int) -> np.ndarray:
    """Block indices :func:`autotune_plan` samples out of ``nb`` blocks.

    Exported so device-parallel callers (repro.core.distributed) can gather
    exactly this sample per shard and hand it back ``presampled`` — the
    plan they obtain is then bit-identical to the in-process tuner's.
    """
    if nb <= EXHAUSTIVE_BLOCKS:
        return np.arange(nb, dtype=np.int64)
    ns = min(nb, max(MIN_SAMPLE_BLOCKS, int(round(SAMPLE_FRACTION * nb))))
    return np.linspace(0, nb - 1, ns).astype(np.int64)  # uniform sampling (paper)


def legacy_sample_indices(nb: int) -> np.ndarray:
    """Block indices the legacy :func:`autotune` samples (no exhaustive tier)."""
    ns = min(nb, max(MIN_SAMPLE_BLOCKS, int(round(SAMPLE_FRACTION * nb))))
    return np.linspace(0, nb - 1, ns).astype(np.int64)


def _sample_blocks(blocks: np.ndarray) -> np.ndarray:
    if blocks.shape[0] <= EXHAUSTIVE_BLOCKS:
        return np.ascontiguousarray(blocks)  # no-copy when already contiguous
    return np.ascontiguousarray(blocks[plan_sample_indices(blocks.shape[0])])


# ------------------------------------------------------------------ tuners
def autotune(blocks: np.ndarray, twoeb: float, levels=(8, 4, 2, 1), anchor_every: int = 16, rng_seed: int = 0,
             presampled: bool = False):
    """Legacy tuner: per-level (spline x scheme) argmin of absolute error.

    blocks: (nb, B..). Returns (splines, schemes) tuples, one entry per level.
    ``presampled=True``: blocks are already the :func:`legacy_sample_indices`
    sample (device-parallel callers gather it shard-side) — skip resampling.
    """
    ndim = blocks.ndim - 1
    B = blocks.shape[1]
    nb = blocks.shape[0]
    sample = to_device(blocks if presampled else blocks[legacy_sample_indices(nb)])
    am = to_device(_anchor_mask(sample.shape[1:], anchor_every))
    recon = jnp.where(am, sample, 0.0)
    qs = quant_steps(0.5 * twoeb)
    chosen_splines, chosen_schemes = [], []
    for s in levels:
        best = None
        for spline in SPLINES:
            for scheme in SCHEMES:
                steps = build_steps(ndim, B, (s,), (spline,), (scheme,))
                _, err = _level_pass(recon, sample, *qs, steps, False)
                err = float(err)
                if best is None or err < best[0]:
                    best = (err, spline, scheme)
        _, spline, scheme = best
        chosen_splines.append(spline)
        chosen_schemes.append(scheme)
        steps = build_steps(ndim, B, (s,), (spline,), (scheme,))
        recon, _ = _level_pass(recon, sample, *qs, steps, True)
    return tuple(chosen_splines), tuple(chosen_schemes)


def _anchor_count(field_shape: tuple[int, ...] | None, sample_shape: tuple[int, ...], n_blocks: int, stride: int) -> int:
    """Anchors the container will store, in full-field units.

    With the real (batch, *padded) field shape this is exact; the
    block-local fallback counts over ALL ``n_blocks`` blocks (not just the
    sample) so it shares units with the scale-extrapolated code bits — it
    overcounts shared faces, but ranks strides consistently.
    """
    if field_shape is not None:
        batch, spatial = field_shape[0], field_shape[1:]
        per = 1
        for d in spatial:
            per *= (d - 1) // stride + 1
        return int(batch) * per
    return n_blocks * int(np.count_nonzero(_anchor_mask(sample_shape, stride)))


def _greedy_levels(sample, qs, stride: int, ndim: int, B: int):
    """Per-level greedy sweep with quantization feedback.

    Returns (splines, schemes, per-level code grids big-stride-first).
    """
    am = to_device(_anchor_mask(sample.shape[1:], stride))
    recon = jnp.where(am, sample, 0.0)
    grids: list[np.ndarray] = []
    splines_sel: list[str] = []
    schemes_sel: list[str] = []
    for s in levels_for_stride(stride):
        level_best = None
        for spline in candidate_splines():
            for scheme in candidate_schemes(ndim):
                steps = build_steps(ndim, B, (s,), (spline,), (scheme,))
                r2, codes = _level_codes_pass(recon, sample, *qs, steps)
                codes = to_host(codes)
                emits = _level_emits(codes)
                hist = np.bincount(emits, minlength=256)
                bits = _code_bits(hist, int(hist[0]))
                if level_best is None or bits < level_best[0]:
                    level_best = (bits, spline, scheme, r2, codes)
        _, spline, scheme, recon, codes = level_best
        grids.append(codes)
        splines_sel.append(spline)
        schemes_sel.append(scheme)
    return tuple(splines_sel), tuple(schemes_sel), grids


def _eval_config(sample, qs, stride: int, splines, schemes, ndim: int, B: int):
    """Full-hierarchy evaluation of a (splines, schemes) config with
    feedback; returns per-level code grids. Runs level by level so every
    jitted pass is shared with the greedy sweep's cache."""
    am = to_device(_anchor_mask(sample.shape[1:], stride))
    recon = jnp.where(am, sample, 0.0)
    grids: list[np.ndarray] = []
    for s, spline, scheme in zip(levels_for_stride(stride), splines, schemes):
        steps = build_steps(ndim, B, (s,), (spline,), (scheme,))
        recon, codes = _level_codes_pass(recon, sample, *qs, steps)
        grids.append(to_host(codes))
    return grids


def autotune_plan(
    blocks: np.ndarray,
    twoeb: float,
    anchor_strides: tuple[int, ...] = DEFAULT_STRIDES,
    field_shape: tuple[int, ...] | None = None,
    trial_pipeline: str = "cr",
    max_trials: int = 6,
    reorder: bool = True,
    presampled_of: int | None = None,
) -> PredictorPlan:
    """Full planner behind ``predictor="auto"``.

    blocks: (nb, B..) anchor blocks (gathered at the block stride);
    ``field_shape``: optional (batch, *padded) shape for an exact anchor
    count in the stride comparison. ``presampled_of=N``: blocks are already
    the :func:`plan_sample_indices` sample of an N-block field (gathered
    shard-side by repro.core.distributed) — skip resampling and scale code
    bits by N/len(blocks), exactly as the in-process path would.

    Mirrors the lossless orchestrator's estimate-then-trial structure,
    per candidate anchor stride:

    1. the paper's greedy per-level sweep, each level scored by the
       entropy of its quantized-residual codes (the shared
       ``stream_stats`` cost model);
    2. every *uniform* (spline, scheme) configuration evaluated
       full-hierarchy with feedback — so the candidate set contains every
       fixed-steps configuration — pre-scored by mixture entropy over all
       levels plus outlier and anchor storage;
    3. the ``max_trials`` best candidates are *trial-encoded* through the
       actual ``trial_pipeline`` encoder and the plan minimizing trialed
       total bytes wins. When the sample is exhaustive (small fields) the
       trial stream is built through the real block-scatter + level
       reorder, so the trial byte count is the realized payload size; on
       sampled fields it falls back to block-local level segments,
       extrapolated to the full field.
    """
    ndim = blocks.ndim - 1
    B = blocks.shape[1]
    if presampled_of is not None:
        nb, sample_np = int(presampled_of), np.ascontiguousarray(blocks)
    else:
        nb, sample_np = blocks.shape[0], _sample_blocks(blocks)
    ns = sample_np.shape[0]
    sample = to_device(sample_np)
    qs = quant_steps(0.5 * twoeb)  # (twoeb, inv2eb) as the encoder quantizes
    scale = nb / ns  # sampled code bits -> full-field code bits
    n_points = nb * B**ndim  # normalization only; comparisons use totals
    exact = ns == nb and field_shape is not None
    cands: list[dict] = []

    def consider(stride, splines, schemes, grids, anchor_bits, tag):
        seq = np.concatenate([_level_emits(g) for g in grids]) if grids else np.zeros(0, np.uint8)
        hist = np.bincount(seq, minlength=256)
        est = (anchor_bits + _code_bits(hist, int(hist[0])) * scale) / max(n_points, 1)
        combined = None
        if exact:  # u8 merge: a quarter of the level grids' footprint
            combined = np.full(sample_np.shape, CENTER, np.int32)  # anchors keep the fill
            for g in grids:
                combined = np.where(g >= 0, g, combined)
            combined = combined.astype(np.uint8)
        cands.append({
            "label": f"{tag}:stride{stride}:" + ",".join(f"{sp}/{sc}" for sp, sc in zip(splines, schemes)),
            "stride": stride, "splines": tuple(splines), "schemes": tuple(schemes),
            "seq": seq, "combined": combined, "n_out": int(hist[0]),
            "anchor_bits": anchor_bits, "est": est,
        })

    for stride in anchor_strides:
        anchor_bits = _anchor_count(field_shape, sample.shape[1:], nb, stride) * ANCHOR_BITS
        nlev = len(levels_for_stride(stride))
        g_splines, g_schemes, g_grids = _greedy_levels(sample, qs, stride, ndim, B)
        consider(stride, g_splines, g_schemes, g_grids, anchor_bits, "greedy")
        for spline in candidate_splines():
            for scheme in candidate_schemes(ndim):
                cfg = ((spline,) * nlev, (scheme,) * nlev)
                if cfg == (g_splines, g_schemes):
                    continue  # already scored as the greedy plan
                grids = _eval_config(sample, qs, stride, *cfg, ndim, B)
                consider(stride, *cfg, grids, anchor_bits, "uniform")

    order = sorted(cands, key=lambda c: (c["est"], c["label"]))[: max(1, max_trials)]
    batch = int(field_shape[0]) if field_shape is not None else 1
    for c in order:
        if exact:
            # the realized stream: scatter the blocks back and apply the
            # level reorder, exactly like the compressor's encode path
            cgrid = _blk.scatter_blocks_batch(c["combined"], batch, tuple(field_shape[1:]), B - 1)
            seq = reorder_codes_batch(cgrid, c["stride"], reorder)
            n_out = int(np.count_nonzero(seq == 0))
        else:
            seq, n_out = c["seq"], c["n_out"]
        code_bits = 8.0 * len(_pipelines.encode(seq, trial_pipeline)) + n_out * OUTLIER_BITS
        c["trial"] = (c["anchor_bits"] + code_bits * (1.0 if exact else scale)) / max(n_points, 1)
    winner = min(order, key=lambda c: (c["trial"], c["label"]))
    return PredictorPlan(
        ndim=ndim,
        anchor_stride=winner["stride"],
        splines=winner["splines"],
        schemes=winner["schemes"],
        est_bits_per_code=winner["trial"],
        sampled_blocks=ns,
        candidates=tuple((c["label"], c.get("trial", c["est"])) for c in cands),
    )
