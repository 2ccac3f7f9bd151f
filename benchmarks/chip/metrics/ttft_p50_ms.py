"""Median time from when a request was due to its first token, over every
request due in the window (one that never answered sorts last)."""

import math

from chipbench import stats


def read(rec):
    ttft = [(r["first_token"] - r["due"]) if r["first_token"] is not None
            else math.inf for r in rec["requests"]]
    p = stats.percentile(ttft, 50)
    return None if p is None or math.isinf(p) else 1000.0 * p
