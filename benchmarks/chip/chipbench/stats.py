"""Percentiles over all samples; nothing is dropped or trimmed."""

from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), over every value.  ``inf`` values
    (requests that never answered) sort last.  None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    frac = rank - lo
    if frac == 0.0:
        return xs[lo]
    if math.isinf(xs[lo + 1]):
        return math.inf
    return xs[lo] + (xs[lo + 1] - xs[lo]) * frac


def sketch_window(start: dict, end: dict) -> dict:
    """The observations a quantile-sketch entry (``QuantileSketch.to_entry``
    of the program's registry) gained between two snapshots: bins and
    counts subtract exactly."""
    bins = {int(k): int(v) for k, v in end.get("bins", {}).items()}
    for k, v in start.get("bins", {}).items():
        bins[int(k)] = bins.get(int(k), 0) - int(v)
    return {"bins": {k: v for k, v in bins.items() if v > 0},
            "zero_count": int(end.get("zero_count", 0))
            - int(start.get("zero_count", 0)),
            "count": int(end.get("count", 0)) - int(start.get("count", 0)),
            "gamma": (1.0 + end["alpha"]) / (1.0 - end["alpha"])}


def sketch_quantile(entry: dict, q: float) -> Optional[float]:
    """Nearest-rank ``q``-quantile (0..1) of a sketch window: the midpoint
    of the bin that holds it, as the sketch itself reports."""
    n = entry["count"]
    if n <= 0:
        return None
    gamma = entry["gamma"]
    rank = q * (n - 1)
    if rank < entry["zero_count"]:
        return 0.0
    acc = entry["zero_count"]
    for i in sorted(entry["bins"]):
        acc += entry["bins"][i]
        if acc > rank:
            return 2.0 * gamma ** i / (gamma + 1.0)
    return None


def percent(part, whole) -> Optional[float]:
    """``100 * part / whole``, or None where nothing was measured."""
    if part is None or not whole or whole <= 0:
        return None
    return 100.0 * part / whole


def roofline_percent(rec) -> Optional[float]:
    """Least time of the traced window's packed kernel calls over their
    device time, in percent; None without kernel events."""
    trace = rec.get("trace")
    if trace is None or trace["kernel_events"] == 0:
        return None
    return percent(trace["kernel_least_s"], trace["kernel_s"])


def idle_percent(rec) -> Optional[float]:
    """Share of the traced window with no operation on the device."""
    trace = rec.get("trace")
    if trace is None:
        return None
    return percent(trace["window_s"] - trace["busy_s"], trace["window_s"])
