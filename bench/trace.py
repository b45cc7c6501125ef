"""From a profiler trace to the numbers the benchmark reports.

``read_xplane`` pulls two things out of a JAX profiler trace: the device
operations of each chip (the ``XLA Ops`` line of each ``/device:`` plane
that has one, named by the program they ran in) and the host spans the
benchmark marked (``TraceAnnotation`` events whose name starts with
``bench:``), all on the trace's one clock in nanoseconds. The rest works on those plain lists, so the tests check it on
a small synthetic trace:

- ``busy_ns``: the union of a chip's operation intervals inside a window;
  the idle share is one minus busy over the window;
- ``top_ops``: device time by operation name;
- ``kernel_ns``: device time of the operations whose instruction is
  named after a kernel;
- ``idle_gaps``: the stretches of the window with no operation running,
  each labelled by the innermost probe open on the host meanwhile.
"""
from __future__ import annotations

import bisect

PREFIX = "bench:"
WINDOW = PREFIX + "window"
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def read_xplane(path) -> dict:
    """``{"devices": {plane: [[op, start_ns, end_ns], ...]},
    "spans": [[probe, start_ns, end_ns], ...]}`` from an ``.xplane.pb``.
    An op is named ``<module>:<instruction>``, e.g.
    ``jit_interp3d_compress:interp3d_compress.1``: the jitted program it ran
    in (its ``XLA Modules`` event, hash dropped) and the HLO instruction's
    name (the event text before `` = ``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        if plane.name.startswith(DEVICE_PLANE) and OPS_LINE in lines:  # a chip, not a trace-only plane
            mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0]) for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = devices.setdefault(plane.name, [])
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "?"
                ops.append([f"{mod}:{e.name.split(' = ', 1)[0].lstrip('%')}", e.start_ns, e.end_ns])
        else:
            for line in plane.lines:
                spans.extend([e.name[len(PREFIX):], e.start_ns, e.end_ns]
                             for e in line.events if e.name.startswith(PREFIX))
    return {"devices": devices, "spans": spans}


def window(trace: dict) -> tuple[float, float]:
    marks = [(s, e) for n, s, e in trace["spans"] if PREFIX + n == WINDOW]
    if len(marks) != 1:
        raise ValueError(f"expected one {WINDOW!r} span in the trace, found {len(marks)}")
    return marks[0]


def merged(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the operations' intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in ops if e > lo and s < hi):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(ops, lo, hi))


def top_ops(devices: dict, lo: float, hi: float, n: int = 10) -> list[list]:
    """Device seconds by operation name inside the window, averaged over
    the chips, largest first."""
    tot: dict[str, float] = {}
    for ops in devices.values():
        for name, s, e in ops:
            if e > lo and s < hi:
                tot[name] = tot.get(name, 0.0) + (min(e, hi) - max(s, lo))
    k = max(len(devices), 1)
    return [[name, ns / k / 1e9] for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def kernel_ns(devices: dict, kernel: str, lo: float, hi: float) -> tuple[float, int]:
    """Device time and count of the operations whose instruction name
    starts with ``kernel`` (a Pallas call's instruction is named after its
    jitted function), summed over the chips."""
    t, n = 0.0, 0
    for ops in devices.values():
        for name, s, e in ops:
            if name.rsplit(":", 1)[-1].startswith(kernel) and e > lo and s < hi:
                t += min(e, hi) - max(s, lo)
                n += 1
    return t, n


def label(spans, s: float, e: float) -> str:
    """The probe that covers most of ``[s, e]``, the innermost among
    equals; "harness" where none is open."""
    best, best_cover, best_len = "harness", 0.0, float("inf")
    for name, a, b in spans:
        if PREFIX + name == WINDOW:
            continue
        cover = min(b, e) - max(a, s)
        if cover > best_cover or (cover == best_cover and cover > 0 and b - a < best_len):
            best, best_cover, best_len = name, cover, b - a
    return best


def idle_gaps(ops, spans, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of ``[lo, hi]`` with no operation on
    the chip, longest first, as ``[label, seconds]``."""
    gaps, t = [], lo
    for s, e in merged(ops, lo, hi) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(spans, s, e), (e - s) / 1e9] for s, e in gaps[:n]]


def reduce(trace: dict) -> dict:
    """Window, busy seconds averaged over the chips, and the breakdown."""
    lo, hi = window(trace)
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = [busy_ns(ops, lo, hi) for ops in devices.values()]
    first = devices[sorted(devices)[0]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "breakdown": {"device_ops": top_ops(devices, lo, hi),
                      "idle_gaps": idle_gaps(first, trace["spans"], lo, hi)},
        "lo": lo, "hi": hi,
    }
