"""The decode work's share of the chip's bf16 peak: operations of every
token decoded in the window (kept packed weights, the head, and attention
over each token's context) over the window times the peak."""

from chipbench.stats import percent


def read(rec):
    if rec["gen_tokens_window"] == 0:
        return None
    return percent(rec["gen_flops_window"],
                   rec["window_s"] * rec["peak"]["bf16_flops_per_s"])
