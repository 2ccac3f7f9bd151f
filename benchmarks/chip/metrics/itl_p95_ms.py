"""95th percentile of the gap between consecutive output tokens of one
request, over every gap whose later token came in the window."""

from chipbench import stats


def read(rec):
    p = stats.percentile(rec["token_gaps_s"], 95)
    return None if p is None else 1000.0 * p
