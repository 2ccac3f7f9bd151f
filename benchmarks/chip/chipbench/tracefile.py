"""Reduce a profiler trace to busy time, kernel time and idle gaps.

:func:`load` reads the ``.xplane.pb`` a ``jax.profiler`` trace writes into
plain event lists: the operations each device ran (the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane) and the benchmark's own host spans (the
``bench.*`` ``TraceAnnotation`` names), all in nanoseconds on the
profiler's one clock.  :func:`reduce` then works on those lists alone, so
it can be checked on a small recorded trace:

* ``busy_s``    — the union of the intervals of the operations (and the
  asynchronous copies) inside the traced window, averaged over the
  devices;
* ``kernel_s``  — the summed device time of the operations whose own HLO
  name starts with ``demm`` (the Pallas kernels are named ``demm_*``), and
  ``kernel_least_s`` the least time the same calls could take;
* ``kernels``   — the same by kernel family, the op name up to its first
  ``.`` (``demm_xwT.46`` is a ``demm_xwT``): ``s``, ``least_s`` and
  ``calls``.  A family's calls are costed by ``kernels/<family>.py`` where
  the benchmark has that file, else by :func:`chipbench.costs.kernel_call`;
* ``device_ops`` — self time by operation (its time less the time of the
  operations nested in it, as a ``while`` holds its body), largest first;
* ``idle_gaps`` — the window's idle time by the innermost host span that
  covers the middle of each gap.

The trace names an operation by its whole HLO text (``%demm_xwT.46 =
f32[...] custom-call(...)``); :func:`op_name` keeps the part before
``" = "``, so an operation that merely reads a kernel's output is not
taken for the kernel.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

from chipbench import costs, spec

KERNEL_MARK = "demm"
WINDOW_SPAN = "bench.window"
NO_SPAN = "outside any bench span"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Trace:
    """Device operations per device, and host spans, as (name, start, end)
    in nanoseconds.  ``copies`` holds each device's asynchronous
    operations, which count as busy time only."""

    devices: Dict[str, List[Tuple[str, int, int]]]
    spans: List[Tuple[str, int, int]]
    copies: Dict[str, List[Tuple[str, int, int]]] = dataclasses.field(
        default_factory=dict)
    # each kernel op's whole HLO text, which holds its operands' shapes
    kernels: Dict[str, str] = dataclasses.field(default_factory=dict)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> str:
    """``%demm_xwT.46 = f32[...] custom-call(...)`` -> ``demm_xwT.46``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, kernels=None) -> List[Tuple[str, int, int]]:
    out = []
    for e in line.events:
        start, name = int(e.start_ns), op_name(e.name)
        out.append((name, start, start + int(e.duration_ns)))
        if kernels is not None and is_kernel(name) and name not in kernels:
            kernels[name] = e.name
    return out


def load(path: str) -> Trace:
    """Device operations and bench spans of one ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, int, int]]] = {}
    copies: Dict[str, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    kernels: Dict[str, str] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = _events(line, kernels)
                elif line.name == "Async XLA Ops":
                    copies[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        start = int(e.start_ns)
                        spans.append((e.name, start,
                                      start + int(e.duration_ns)))
    return Trace(devices=devices, spans=spans, copies=copies,
                 kernels=kernels)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int) -> Interval:
    return max(s, lo), min(e, hi)


def window_of(trace: Trace) -> Interval:
    for name, s, e in trace.spans:
        if name == WINDOW_SPAN:
            return s, e
    starts = [s for ev in trace.devices.values() for _, s, _ in ev]
    ends = [e for ev in trace.devices.values() for _, _, e in ev]
    if not starts:
        raise ValueError("the trace holds no device operation and no "
                         f"{WINDOW_SPAN} span")
    return min(starts), max(ends)


def is_kernel(name: str) -> bool:
    return name.startswith(KERNEL_MARK)


def family(name: str) -> str:
    """``demm_xwT.46`` -> ``demm_xwT``."""
    return name.split(".", 1)[0]


def least_times(kernels: Dict[str, str], peak: dict,
                bench_dir: str) -> Dict[str, float]:
    """The least time of each kernel op's call, from its HLO text, by its
    family's ``kernels/<family>.py`` or :func:`chipbench.costs.kernel_call`
    where the family has none."""
    calls: Dict[str, object] = {}
    out = {}
    for name, text in kernels.items():
        fam = family(name)
        if fam not in calls:
            calls[fam] = (spec.load_kernel_call(bench_dir, fam)
                          or costs.kernel_call)
        out[name] = calls[fam](text).least_s(peak)
    return out


def self_times(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """Time of each operation less the time of the operations nested in
    it, summed by name."""
    out: Dict[str, int] = {}
    stack: List[list] = []          # [name, end, duration, nested]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0) + entry[2] - entry[3]

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            close(stack.pop())          # ended, or only overlaps this one
        if stack:
            stack[-1][3] += e - s
        stack.append([name, e, e - s, 0])
    while stack:
        close(stack.pop())
    return out


def _span_at(spans: List[Tuple[str, int, int]], starts: List[int],
             t: float) -> str:
    """The latest-starting span that covers ``t`` (the bench's spans inside
    the window follow one another; ``spans`` sorted by start)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, e = spans[i]
        if t < e:
            return name
        i -= 1
        if i >= 0 and spans[i][2] <= s:
            break               # an earlier span ended before this one began
    return NO_SPAN


def reduce(trace: Trace, peak: Optional[dict] = None,
           window: Optional[Interval] = None, top: int = 10,
           bench_dir: str = spec.BENCH_DIR) -> dict:
    """The traced window's numbers.  The window ends no later than the
    last recorded device operation: a profiler whose event buffer fills
    records nothing after it, and that silence is not idle time.  With
    ``peak``, ``kernel_least_s`` sums the least time of every kernel call
    in the window (:func:`least_times`, from the kernel cost files under
    ``bench_dir``, the benchmark's own by default)."""
    lo, hi = window or window_of(trace)
    last = max((e for ev in trace.devices.values() for _, _, e in ev),
               default=hi)
    hi = min(hi, last)
    if hi <= lo:
        raise ValueError(f"empty traced window {lo}..{hi}")
    n_dev = max(len(trace.devices), 1)
    least = least_times(trace.kernels, peak, bench_dir) if peak else {}
    busy_ns = 0
    by_family: Dict[str, list] = {}     # family -> [ns, least_s, calls]
    by_op: Dict[str, int] = {}
    gaps: List[Interval] = []
    for dev, events in trace.devices.items():
        clipped = []
        for name, s, e in events:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            clipped.append((name, s, e))
            if is_kernel(name):
                fam = by_family.setdefault(family(name), [0, 0.0, 0])
                fam[0] += e - s
                fam[1] += least.get(name, 0.0)
                fam[2] += 1
        for name, t in self_times(clipped).items():
            by_op[name] = by_op.get(name, 0) + t
        intervals = [(s, e) for _, s, e in clipped]
        for _, s, e in trace.copies.get(dev, []):
            intervals.append(_clip(s, e, lo, hi))
        covered = union(intervals)
        busy_ns += sum(e - s for s, e in covered)
        edge = lo
        for s, e in covered:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi > edge:
            gaps.append((edge, hi))
    idle: Dict[str, int] = {}
    inside = sorted((sp for sp in trace.spans if sp[0] != WINDOW_SPAN),
                    key=lambda sp: sp[1])
    starts = [sp[1] for sp in inside]
    for s, e in gaps:
        name = _span_at(inside, starts, (s + e) / 2)
        idle[name] = idle.get(name, 0) + (e - s)
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    ranked_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "kernel_s": sum(f[0] for f in by_family.values()) / n_dev / 1e9,
        "kernel_events": sum(f[2] for f in by_family.values()),
        "kernel_least_s": sum(f[1] for f in by_family.values()) / n_dev,
        "kernels": {fam: {"s": ns / n_dev / 1e9, "least_s": lst / n_dev,
                          "calls": calls}
                    for fam, (ns, lst, calls) in sorted(by_family.items())},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in ranked],
        "idle_gaps": [[k, v / n_dev / 1e9] for k, v in ranked_idle],
        "gaps": len(gaps),
    }
