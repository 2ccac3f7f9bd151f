"""Median time from when a request was due to when the engine first gave
it a slot, over the requests due in the window that got one."""

from chipbench import stats


def read(rec):
    waits = [r["claim"] - r["due"] for r in rec["requests"]
             if r["claim"] is not None]
    p = stats.percentile(waits, 50)
    return None if p is None else 1000.0 * p
