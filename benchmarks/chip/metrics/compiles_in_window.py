"""Executables the engine's two compiled programs traced and compiled in
the window (``serve_compiles_total``, decode and prefill): set-up warms
every shape, so this reads 0."""


def read(rec):
    counters = rec["counters_window"]
    found = [counters[k] for k in ("serve_compiles_total{program=decode}",
                                   "serve_compiles_total{program=prefill}")
             if k in counters]
    return sum(found) if found else None
