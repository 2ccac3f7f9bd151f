"""``repro.obs`` — metrics, event tracing, structured logging, profiling.

The observability layer every perf claim in this repo is judged against
(DESIGN.md §12).  Dependency-free (stdlib + jax only):

* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` with counters, gauges,
  and fixed-bucket histograms; JSON-snapshot and Prometheus-text exporters.
* :mod:`repro.obs.trace`   — JSONL event trace (:class:`Span` / ``event()``
  with monotonic timestamps), attached to each registry as ``.trace``.
* :mod:`repro.obs.log`     — level-filtered structured logger (text or JSON
  lines) used by the ``launch/`` drivers.
* :mod:`repro.obs.profile` — profiling: ``annotate(name)`` names DeMM
  kernels in profiler traces, ``phase(name, counter)`` puts a span of host
  work on the profiler's clock and its seconds in a counter,
  ``profile(trace_dir)`` dumps a jax profiler trace directory for
  TensorBoard/perfetto.

Observability v2 (DESIGN.md §16) adds:

* :mod:`repro.obs.context`  — contextvar trace context (``trace_id`` /
  span ids / attribution labels) created per request at ``submit()`` and
  spliced into every trace event emitted on the request's behalf.
* :mod:`repro.obs.sketch`   — :class:`QuantileSketch`, a DDSketch-style
  mergeable relative-error quantile sketch; fourth registry family kind.
* :mod:`repro.obs.slo`      — per-request phase attribution, goodput /
  wasted-token accounting, SLO pass-fail reports.
* :mod:`repro.obs.recorder` — :class:`FlightRecorder` (bounded
  per-subsystem event rings + stall watchdogs + crash/signal dumps).
* :mod:`repro.obs.export`   — JSONL trace → Perfetto/Chrome trace JSON
  (``python -m repro.obs.export``).

The process-wide default registry (:func:`metrics`) is what the kernel
dispatch counters, the tuning-cache hit/miss counters, the serve engine, and
the training supervisor share by default, so ``launch/serve.py
--metrics-out metrics.json`` captures one coherent snapshot across all four
subsystems.  Tests (and anything wanting isolation) construct their own
:class:`MetricsRegistry` or swap the default with
:func:`set_default_registry`.
"""

from __future__ import annotations

from repro.obs.context import TraceContext, current_context, new_trace_id
from repro.obs.context import use as use_context
from repro.obs.log import LEVELS, StructuredLogger, get_logger
from repro.obs.metrics import (
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    run_metadata,
    set_default_registry,
)
from repro.obs.profile import annotate, phase, profile
from repro.obs.recorder import FlightRecorder, Watchdog
from repro.obs.sketch import QuantileSketch
from repro.obs.slo import SLOConfig, phase_sketches, request_phases, slo_report
from repro.obs.trace import EventTrace, Span

__all__ = [
    "DEFAULT_TIME_BUCKETS", "Counter", "EventTrace", "FlightRecorder",
    "Gauge", "Histogram", "LEVELS", "MetricsRegistry", "QuantileSketch",
    "SLOConfig", "Span", "StructuredLogger", "TraceContext", "Watchdog",
    "annotate", "current_context", "default_registry", "event",
    "get_logger", "metrics", "new_trace_id", "phase", "phase_sketches",
    "profile", "request_phases", "run_metadata",
    "set_default_registry", "slo_report", "use_context",
]


def metrics() -> MetricsRegistry:
    """The process-wide default :class:`MetricsRegistry` (see module doc)."""
    return default_registry()


def event(name: str, **attrs) -> dict:
    """Record a point event on the default registry's trace."""
    return default_registry().trace.event(name, **attrs)
