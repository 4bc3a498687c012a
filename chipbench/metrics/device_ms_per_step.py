"""Device busy milliseconds per engine step in the traced window."""


def read(r):
    t = r["trace"]
    if not t or not r["engine_steps"]:
        return None
    return 1e3 * t["busy_s"] / r["engine_steps"]
