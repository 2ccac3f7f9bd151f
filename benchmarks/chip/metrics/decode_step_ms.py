"""Median decode step, as the engine's own sketch of per-token decode
latency saw it during the window (dispatch to logits on the host)."""

from chipbench import stats


def read(rec):
    q = stats.sketch_quantile(rec["decode_step_sketch"], 0.5)
    return None if q is None else 1000.0 * q
