"""Device busy milliseconds per engine step of one instance in the traced
window (busy time averaged over the chips, steps shared among the
instances, one to a chip)."""


def read(r):
    t = r["trace"]
    if not t or not r["engine_steps"]:
        return None
    return 1e3 * t["busy_s"] / (r["engine_steps"] / r["instances"])
