"""Rows each streamed expert serves: the expert assignments the engine
routed in the window over the experts that received at least one row,
summed over layers and over both programs (Δ``serve_moe_rows_total`` over
Δ``serve_moe_experts_touched_total``).  Few rows a touch leave the grouped
kernel bound by the bytes of the experts it streams; many, by compute.
None where the program counts no routing (a dense model, or a program
without the counters)."""


def read(rec):
    counters = rec["counters_window"]

    def total(name):
        found = [counters[k] for k in (f"{name}{{program=decode}}",
                                       f"{name}{{program=prefill}}")
                 if k in counters]
        return sum(found) if found else None

    rows = total("serve_moe_rows_total")
    touched = total("serve_moe_experts_touched_total")
    if rows is None or not touched:
        return None
    return rows / touched
