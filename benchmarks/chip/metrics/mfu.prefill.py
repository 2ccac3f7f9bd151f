"""The prefill work's share of the chip's bf16 peak: operations of every
prompt token first ingested in the window (kept packed weights and
attention over its context, plus the head once per finished prefill) over
the window times the peak."""

from chipbench.stats import percent


def read(rec):
    if rec["prompt_tokens_window"] == 0:
        return None
    return percent(rec["prompt_flops_window"],
                   rec["window_s"] * rec["peak"]["bf16_flops_per_s"])
