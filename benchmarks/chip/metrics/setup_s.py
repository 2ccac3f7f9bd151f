"""Set-up: from process start to the window's opening (loading, weights,
the engine, warming both programs, and the traffic's pre-roll)."""


def read(rec):
    return rec["setup_s"]
