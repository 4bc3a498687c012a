"""Window milliseconds per engine step (host clock over the instances'
step counter)."""


def read(r):
    if not r["engine_steps"]:
        return None
    return 1e3 * r["window_s"] / r["engine_steps"]
