"""The plain reference of the configurations' guarantees, and its control.

Nothing here imports the program or reads anything it made. An
error-bounded compressor guarantees that every decoded point lies within
the declared bound of the input; the bound is ``eb`` (``eb_mode
"abs"``) or ``eb`` times the input's value range (``"rel"``), worked out
here from the input in float64, with ``bound_slack`` of room for the
float32 rounding of the decoded value (the program's systemwide
``1 + 1e-4``).

``plain_codec`` is the straightforward codec with that guarantee: round
every point to the nearest multiple of ``2 * eb`` above the minimum. In
float32 it keeps the bound; computed in bfloat16, the nearest precision
below the configurations' float32, it is the control, which the check
must refuse.
"""
from __future__ import annotations

import numpy as np

SLAB = 1 << 24  # points per step of the float64 comparisons (128 MiB)


def bound_abs(vmin: float, vmax: float, cfg: dict) -> float:
    spec = cfg["spec"]
    eb = float(spec.get("eb", 1e-3))
    mode = spec.get("eb_mode", "rel")
    if mode == "abs":
        return eb
    if mode != "rel":
        raise ValueError(f"no plain reference for eb_mode {mode!r}")
    return eb * (float(vmax) - float(vmin))


def err_over_bound(x: np.ndarray, y: np.ndarray, eb_abs: float) -> float:
    """``max |y - x| / eb_abs`` over every point, in float64; ``inf`` for
    a shape mismatch and NaN-proof (a NaN point reads ``inf``)."""
    x, y = np.asarray(x).reshape(-1), np.asarray(y).reshape(-1)
    if x.shape != y.shape:
        return float("inf")
    worst = 0.0
    for i in range(0, x.size, SLAB):
        d = np.abs(y[i:i + SLAB].astype(np.float64) - x[i:i + SLAB])
        m = float(np.max(d, initial=0.0))
        if not np.isfinite(m):  # NaN propagates through max
            return float("inf")
        worst = max(worst, m)
    return worst / eb_abs if eb_abs > 0 else (0.0 if worst == 0 else float("inf"))


def diff_points(a: np.ndarray, b: np.ndarray) -> int:
    """Points whose float32 bit patterns differ; every point for a shape
    mismatch."""
    a, b = np.asarray(a, np.float32).reshape(-1), np.asarray(b, np.float32).reshape(-1)
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int(sum(np.count_nonzero(a[i:i + SLAB].view(np.uint32) != b[i:i + SLAB].view(np.uint32))
                   for i in range(0, a.size, SLAB)))


def plain_codec(x, eb_abs: float, dtype):
    """Decode of the plain uniform quantizer at bound ``eb_abs``, computed
    in ``dtype`` (``jnp.float32`` keeps the bound, ``jnp.bfloat16`` is the
    control). ``x`` may live on the device; the result has ``x``'s
    residency."""
    import jax.numpy as jnp

    lo = jnp.min(x).astype(dtype)
    step = jnp.asarray(2.0 * eb_abs, dtype)
    q = jnp.round((x.astype(dtype) - lo) / step)
    return (q * step + lo).astype(jnp.float32)
