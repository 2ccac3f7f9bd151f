"""Profiling hooks: named kernels, host phases on the profiler's clock.

``annotate(name)`` wraps a region in ``jax.named_scope`` — zero steady-state
cost: named scopes only exist at trace time, where they stamp the HLO ops
(and therefore the Pallas kernel launches lowered from them) with a
hierarchical name.  The kernel dispatch path wraps every DeMM matmul in
``demm/<op>/<backend>`` scopes, so a TensorBoard/perfetto trace shows which
registry variant each kernel launch came from.

``phase(name, counter)`` marks a stretch of host work: a
``jax.profiler.TraceAnnotation`` that any attached profiler records on the
device trace's clock, and the elapsed seconds added to ``counter``.  The
paged engine runs every tick phase under one (``serve.admit``,
``serve.decode.wait``, ...), so a trace can say what the host was doing in
each idle gap of the device.  With no profiler attached a span costs one
``TraceMe`` check.

``profile(trace_dir)`` brackets the region with
``jax.profiler.start_trace``/``stop_trace`` and dumps the trace directory
for TensorBoard (``tensorboard --logdir <trace_dir>``) or perfetto::

    with obs.profile("/tmp/serve_trace"):
        engine.run_until_drained()

``launch/serve.py --profile-dir DIR`` is the CLI spelling.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional


@contextlib.contextmanager
def profile(trace_dir):
    """Capture a jax profiler trace of the enclosed region into
    ``trace_dir`` (Pallas kernels appear under their ``annotate`` names,
    engine phases under their ``phase`` names)."""
    import jax

    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str):
    """Name the enclosed computation with a ``jax.named_scope`` (HLO op
    names → named kernels in profiler traces)."""
    import jax

    with jax.named_scope(name):
        yield


class phase:
    """Run the enclosed host work under a profiler span ``name`` carrying
    ``meta`` (a ``StepTraceAnnotation`` numbered ``step_num`` when given),
    and add its elapsed ``time.perf_counter`` seconds to ``counter``.  A
    class, not a generator, since the engine opens ~15 a tick."""

    __slots__ = ("_span", "_counter", "_t0")

    def __init__(self, name: str, counter=None, *,
                 step_num: Optional[int] = None, **meta):
        import jax

        self._span = (
            jax.profiler.TraceAnnotation(name, **meta) if step_num is None
            else jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                                  **meta))
        self._counter = counter

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._counter is not None:
            self._counter.inc(time.perf_counter() - self._t0)
        return self._span.__exit__(*exc)
