"""The device a run measures, and the peaks it is held against.

There is no CPU fallback: a run that finds no TPU, fewer chips than the
cell asks for, or a ``device_kind`` missing from ``peaks.json`` stops before
it measures anything.
"""

from __future__ import annotations

import json
import os

from chipbench.spec import BENCH_DIR


class DeviceError(RuntimeError):
    """The machine cannot run this cell."""


def load_peaks(path: str = os.path.join(BENCH_DIR, "peaks.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def check(devices, chips: int, peaks: dict) -> dict:
    """The peaks entry for ``devices`` (JAX devices), or DeviceError."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        raise DeviceError(f"no TPU found (JAX backend is {platform!r})")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, found "
                          f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise DeviceError(f"device kind {kind!r} has no entry in peaks.json "
                          f"(known: {sorted(peaks)})")
    return peaks[kind]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device, 0 where not reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))
