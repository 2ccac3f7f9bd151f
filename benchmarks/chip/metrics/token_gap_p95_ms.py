"""95th percentile of the gap between consecutive output tokens of one
request, over every gap whose later token came in the window.  In the chat
cell it lies at the edge between plain decode ticks and ticks that also
run prefill chunks, so it swings from run to run: a per-layer reading of
the scheduler's interleave, not an end-to-end metric."""

from chipbench import stats


def read(rec):
    p = stats.percentile(rec["token_gaps_s"], 95)
    return None if p is None else 1000.0 * p
