"""Process start to window start: weights, compile or cache reads,
warm-up (host clock)."""


def read(r):
    return r["setup_s"]
