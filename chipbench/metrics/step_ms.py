"""Window milliseconds per engine step of one instance (host clock over
the instances' step counter, shared among the instances, which step side
by side)."""


def read(r):
    if not r["engine_steps"]:
        return None
    return 1e3 * r["window_s"] / (r["engine_steps"] / r["instances"])
