"""Host time of an engine tick outside the device waits: the engine's own
``serve.tick`` seconds less its ``serve.decode.wait`` and
``serve.prefill.wait`` seconds (``serve_phase_seconds_total``), over the
ticks run in the window (``serve_ticks_total``)."""


def read(rec):
    counters = rec["counters_window"]
    ticks = counters.get("serve_ticks_total")
    tick = counters.get("serve_phase_seconds_total{phase=tick}")
    if not ticks or tick is None:
        return None
    waits = sum(counters.get(f"serve_phase_seconds_total{{phase={p}}}", 0.0)
                for p in ("decode_wait", "prefill_wait"))
    return 1000.0 * (tick - waits) / ticks
