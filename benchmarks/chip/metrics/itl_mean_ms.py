"""Mean gap between consecutive output tokens of one request, over every
gap whose later token came in the window: the time a reader waits for each
next token, averaged over all of them, prefill-carrying ticks included."""


def read(rec):
    gaps = rec["token_gaps_s"]
    return 1000.0 * sum(gaps) / len(gaps) if gaps else None
