"""What the per-layer metric readers share: the run they read, and the
arithmetic of a probe's share, the device's idle share and a kernel's
share of its HBM roofline. Each reader returns ``None`` where the run
holds nothing for it to read, never 0 for a share it could not measure.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

from . import trace as tr

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass
class Run:
    cfg: dict
    window_s: float            # host clock, first call's start to last call's end
    self_s: dict               # probe -> self seconds in the window
    device_kind: str
    trace: dict | None = None  # read_xplane's lists, traced runs only
    reduced: dict | None = None


def peaks(device_kind: str, path=PEAKS) -> dict:
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def probe_share(run: Run, probe: str) -> float | None:
    if probe not in run.self_s or run.window_s <= 0:
        return None
    return 100.0 * run.self_s[probe] / run.window_s


def device_idle(run: Run) -> float | None:
    if run.reduced is None or run.reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.reduced["busy_s"] / run.reduced["window_s"])


def hbm_roofline(run: Run, kernel: str, bytes_per_call) -> float | None:
    """The least time the kernel's bytes take at the chip's HBM rate over
    its measured device time, in %. ``bytes_per_call(run)`` counts one
    call's bytes from the shapes."""
    if run.reduced is None:
        return None
    ns, n = tr.kernel_ns(run.trace["devices"], kernel, run.reduced["lo"], run.reduced["hi"])
    if n == 0 or ns <= 0:
        return None
    least_s = n * bytes_per_call(run) / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
