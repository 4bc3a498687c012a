"""Accepted over drafted speculative tokens, in %."""


def read(r):
    if not r["drafted"]:
        return None
    return 100.0 * r["accepted"] / r["drafted"]
