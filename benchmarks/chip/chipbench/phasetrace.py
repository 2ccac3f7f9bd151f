"""The program's own spans and compiled programs in a profiler trace.

:mod:`chipbench.tracefile` reads the device's operations and the
benchmark's ``bench.*`` spans.  The paged engine also runs every tick phase
under a ``serve.*`` span (``serve.tick``, ``serve.admit``,
``serve.decode.dispatch``, ``serve.decode.wait``, ...) and names its two
compiled programs, which each TPU plane's ``XLA Modules`` line shows as
``jit_decode_step`` and ``jit_prefill_chunk``.  :func:`load` reads both as
well; :func:`reduce` returns every number of :func:`tracefile.reduce`
unchanged and adds:

* ``programs``      — per module name, the ``count``, ``median_ms`` and
  ``total_s`` of its executions in the window (``total_s`` clipped to the
  window and averaged over the devices, as ``kernel_s`` is);
* ``clock_skew_ms`` — how far device events appear to start before the
  dispatch span that caused them (:func:`clock_skew_ns`);
* ``idle_gaps``     — the window's idle time by the innermost span,
  ``serve.*`` or ``bench.*``, that covers the middle of each gap, with the
  spans moved onto the device's clock by ``clock_skew_ms``.  Busy and idle
  totals come from device events alone, as before;
* ``idle_split``    — the same idle time cut at every span boundary, each
  piece under the innermost span that covers it: a gap that runs from the
  end of one program through several host phases to the next dispatch is
  shared among them, where ``idle_gaps`` gives it all to its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Tuple

from chipbench import tracefile

PHASE_MARK = "serve."
MODULES_LINE = "XLA Modules"
# the dispatch span of each named program
DISPATCH_SPAN = {"jit_decode_step": "serve.decode.dispatch",
                 "jit_prefill_chunk": "serve.prefill.dispatch"}
# a dispatch starts at most this long after its execution appears to start
PAIR_NS = 5_000_000

Event = Tuple[str, int, int]


@dataclasses.dataclass
class ProgramTrace(tracefile.Trace):
    """A :class:`tracefile.Trace` whose ``spans`` also hold the program's
    ``serve.*`` spans, with each device's compiled-program executions."""

    modules: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)


def span_name(name: str) -> str:
    """``serve.prefill.dispatch#uid=3#`` -> ``serve.prefill.dispatch``."""
    return name.split("#", 1)[0]


def module_name(name: str) -> str:
    """``jit_decode_step(11769387255970495299)`` -> ``jit_decode_step``."""
    return name.split("(", 1)[0]


def load(path: str) -> ProgramTrace:
    """:func:`tracefile.load`, plus the ``serve.*`` host spans and each TPU
    plane's ``XLA Modules`` line."""
    import jax

    base = tracefile.load(path)
    spans = list(base.spans)
    modules: Dict[str, List[Event]] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (module_name(e.name), int(e.start_ns),
                         int(e.start_ns) + int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PHASE_MARK):
                        start = int(e.start_ns)
                        spans.append((span_name(e.name), start,
                                      start + int(e.duration_ns)))
    return ProgramTrace(devices=base.devices, spans=spans,
                        copies=base.copies, kernels=base.kernels,
                        modules=modules)


def _rank(span: Event):
    name, s, e = span
    return s, name.startswith(PHASE_MARK), -(e - s)


def span_at(spans: List[Event], starts: List[int], longest: int,
            t: float) -> str:
    """The innermost span that covers ``t``: of those covering it, the
    latest to start; at one start a ``serve.*`` span ahead of a
    ``bench.*`` one, then the shorter.  ``spans`` sorted by start, none
    longer than ``longest``."""
    best = None
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and starts[i] >= t - longest:
        if t < spans[i][2] and (best is None or _rank(spans[i]) > _rank(best)):
            best = spans[i]
        i -= 1
    return tracefile.NO_SPAN if best is None else best[0]


def clock_skew_ns(trace: ProgramTrace) -> Optional[int]:
    """How far device events appear to start before the dispatch that
    caused them, at most (0 where none does; None without a pair).

    One device runs a program's executions in the order they were
    dispatched, so the k-th recorded execution pairs with the (k + j)-th
    dispatch span of the same program.  The device tracer may start late,
    so j is not 0: it is the largest shift at which no paired dispatch
    starts more than ``PAIR_NS`` after its execution."""
    leads = []
    for module, span in DISPATCH_SPAN.items():
        starts = sorted(s for name, s, _ in trace.spans if name == span)
        for events in trace.modules.values():
            execs = sorted(s for name, s, _ in events if name == module)
            if not execs or not starts:
                continue
            latest = [bisect.bisect_right(starts, s + PAIR_NS) - 1
                      for s in execs]
            j = min(m - k for k, m in enumerate(latest))
            leads += [starts[k + j] - s for k, s in enumerate(execs)
                      if 0 <= k + j < len(starts)]
    if not leads:
        return None
    return max(0, max(leads))


def window(trace: tracefile.Trace) -> Tuple[int, int]:
    """The window :func:`tracefile.reduce` reads."""
    lo, hi = tracefile.window_of(trace)
    last = max((e for ev in trace.devices.values() for _, _, e in ev),
               default=hi)
    return lo, min(hi, last)


def device_gaps(trace: tracefile.Trace, lo: int,
                hi: int) -> List[Tuple[int, int]]:
    """Every device's stretches of ``lo..hi`` with no operation and no
    asynchronous copy running."""
    gaps = []
    for dev, events in trace.devices.items():
        intervals = [tracefile._clip(s, e, lo, hi) for _, s, e in events]
        intervals += [tracefile._clip(s, e, lo, hi)
                      for _, s, e in trace.copies.get(dev, [])]
        edge = lo
        for s, e in tracefile.union(intervals):
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi > edge:
            gaps.append((edge, hi))
    return gaps


def programs(trace: ProgramTrace, lo: int, hi: int) -> Dict[str, dict]:
    """Executions of each compiled program that overlap ``lo..hi``."""
    n_dev = max(len(trace.devices), 1)
    runs: Dict[str, List[Event]] = {}
    for events in trace.modules.values():
        for name, s, e in events:
            if min(e, hi) > max(s, lo):
                runs.setdefault(name, []).append((name, s, e))
    return {name: {
        "count": len(ev),
        "median_ms": statistics.median(e - s for _, s, e in ev) / 1e6,
        "total_s": sum(min(e, hi) - max(s, lo) for _, s, e in ev)
        / n_dev / 1e9,
    } for name, ev in sorted(runs.items())}


def reduce(trace: ProgramTrace, peak: Optional[dict] = None,
           top: int = 10) -> dict:
    """:func:`tracefile.reduce`'s numbers, ``idle_gaps`` named by the
    innermost span, plus ``idle_split``, ``programs`` and
    ``clock_skew_ms``."""
    out = tracefile.reduce(trace, peak, top=top)
    lo, hi = window(trace)
    skew = clock_skew_ns(trace)
    inside = sorted((sp for sp in trace.spans
                     if sp[0] != tracefile.WINDOW_SPAN),
                    key=lambda sp: sp[1])
    starts = [s for _, s, _ in inside]
    longest = max((e - s for _, s, e in inside), default=0)
    bounds = sorted({t for _, s, e in inside for t in (s, e)})
    n_dev = max(len(trace.devices), 1)
    idle: Dict[str, int] = {}
    split: Dict[str, int] = {}
    shift = skew or 0       # spans are on the host's clock, this far ahead
    for s, e in device_gaps(trace, lo, hi):
        s, e = s + shift, e + shift
        name = span_at(inside, starts, longest, (s + e) / 2)
        idle[name] = idle.get(name, 0) + (e - s)
        cuts = [s] + bounds[bisect.bisect_right(bounds, s):
                            bisect.bisect_left(bounds, e)] + [e]
        for a, b in zip(cuts, cuts[1:]):
            name = span_at(inside, starts, longest, (a + b) / 2)
            split[name] = split.get(name, 0) + (b - a)

    def ranked(by_name):
        return [[k, v / n_dev / 1e9] for k, v in
                sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]

    out["idle_gaps"] = ranked(idle)
    out["idle_split"] = ranked(split)
    out["programs"] = programs(trace, lo, hi)
    out["clock_skew_ms"] = None if skew is None else skew / 1e6
    return out
