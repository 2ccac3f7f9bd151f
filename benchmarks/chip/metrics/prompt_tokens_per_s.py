"""Prompt tokens ingested for the first time in the window, over the whole
window (a re-ingest after preemption does not count)."""


def read(rec):
    return rec["prompt_tokens_window"] / rec["window_s"]
