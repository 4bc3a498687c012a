"""From a profiler trace (``.xplane.pb``) to device busy and idle time, the
device operations that took the most time, and the longest idle gaps named
by what the host was doing in them.

Device planes are those named ``/device:TPU:<n>``; on each, the line of
XLA operations holds one event per operation run.  Busy time is the union
of those intervals (operations on one core may nest or overlap), averaged
over the chips used.  Host events are every event on the ``/host:CPU``
plane; a gap is named by the host event that covers most of it (the
shortest such event on a tie, which is the most specific).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
# the harness brackets its measured window with a host annotation of
# this name; the traced window is that event's span
WINDOW_EVENT = "chipbench.window"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def busy_seconds(busy: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy)


NAME_CHARS = 200     # an XLA op's name holds its whole HLO line


def top(totals: Dict[str, float], n: int = 10) -> List[list]:
    return [[k[:NAME_CHARS], v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def name_gap(gap: Interval, host: Sequence[Tuple[str, float, float]]
             ) -> str:
    """The host event covering most of ``gap`` (shortest on a tie)."""
    best, key = "(no host event)", None
    s, e = gap
    for name, hs, he in host:
        cover = min(e, he) - max(s, hs)
        if cover <= 0:
            continue
        k = (-cover, he - hs)
        if key is None or k < key:
            best, key = name, k
    return best


def reduce_events(device: Dict[str, List[Tuple[str, float, float]]],
                  host: List[Tuple[str, float, float]],
                  lo: float, hi: float) -> Optional[dict]:
    """``device``: chip -> [(op, start_s, end_s)]; ``host``: [(name,
    start_s, end_s)]; the traced window is [lo, hi].  None when no
    device operation was recorded."""
    if not any(device.values()):
        return None
    per_chip, op_time, all_gaps = [], {}, []
    for chip, evs in device.items():
        cover = union((s, e) for _, s, e in evs)
        per_chip.append(busy_seconds(cover, lo, hi))
        for name, s, e in evs:
            op_time[name] = op_time.get(name, 0.0) + (e - s)
        all_gaps += gaps(cover, lo, hi)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": sum(per_chip) / len(per_chip),
        "window_s": hi - lo,
        "device_ops": top(op_time),
        "idle_gaps": [[name_gap(g, host), g[1] - g[0]] for g in longest],
    }


def read_xspace(path: str):
    """(device events, host events, lo_s, hi_s) from an ``.xplane.pb``.
    Times are seconds on the trace's own clock.  The window is the span
    of the :data:`WINDOW_EVENT` annotation, else that of the device
    events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    evs.append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    marks = [(s, e) for name, s, e in host if name == WINDOW_EVENT]
    if marks:
        lo, hi = marks[0]
        host = [h for h in host if h[0] != WINDOW_EVENT]
    else:
        spans = [(s, e) for evs in device.values() for _, s, e in evs]
        lo = min((s for s, _ in spans), default=0.0)
        hi = max((e for _, e in spans), default=0.0)
    return device, host, lo, hi


def reduce_xspace(path: str) -> Optional[dict]:
    return reduce_events(*read_xspace(path))
