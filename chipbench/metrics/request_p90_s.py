"""90th percentile, over every request the window completed, of its
finish time less the start of its iteration (host clock)."""
from statistics import quantiles


def read(r):
    lat = r["latencies"]
    if len(lat) < 2:
        return None
    return quantiles(lat, n=10, method="inclusive")[8]
