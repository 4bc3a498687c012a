"""Share of the iterations' makespans spent after 90% of their requests
had finished, in %: the tail the scheduler has to shorten."""
import math


def read(r):
    its = [i for i in r["iterations"] if i["finish"]]
    total = sum(i["makespan"] for i in its)
    if not total:
        return None
    tail = 0.0
    for i in its:
        f = i["finish"]
        tail += i["makespan"] - f[math.ceil(0.9 * len(f)) - 1]
    return 100.0 * tail / total
