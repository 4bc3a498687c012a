"""Where the device's idle time goes, and what its busy time runs, from a
profiler trace (``.xplane.pb``) of a window in which a ``repro.obs``
``Tracer`` was attached to the rollout.

The tracer mirrors each tick (``seer.tick``) and each host phase of the
tick (``seer.drafts``, ``seer.dispatch``, ...) as a profiler annotation
on the ``/host:CPU`` plane, on the clock of the device's operations.
Each idle instant of the traced window (no operation running on the
chip) is charged to exactly one phase: the innermost ``seer.*`` span
covering it (the latest to start; the shortest on a tie), or
``unattributed`` where none does.  The charges partition the idle time.

Busy time is read per program from the device plane's ``XLA Modules``
line (the engine names its programs ``seer_step_t{T}``,
``seer_tree_step_t{T}``, ``seer_export`` and ``seer_import``), and per
named scope from each operation's ``tf_op`` path (``jit(<program>)/
.../attention/...``; a fusion carries its root operation's path).
``jax.profiler.ProfileData`` does not expose that path, so
:func:`op_scopes` reads it from the device planes' event metadata in
the protobuf wire format.

A program that attaches no tracer leaves no ``seer.*`` span: the
reduction then returns None.
"""
from __future__ import annotations

import bisect
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench.trace_reduce import (DEVICE_PREFIX, busy_seconds, gaps,
                                    read_xspace, union)

Span = Tuple[str, float, float]         # (name, start_s, end_s)

PHASE_PREFIX = "seer."
TICK = "seer.tick"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "unattributed"
STEP_PROGRAMS = ("seer_step_t", "seer_tree_step_t")
#: the host event of a program's launch by the TPU runtime
LAUNCH = "PJRT_LoadedExecutable_Execute"

#: Each reading and the phases whose idle time it sums.  With
#: ``unattributed`` they cover every idle instant.
GROUPS = {
    "draft": ("seer.drafts", "seer.cst_update"),
    "schedule": ("seer.admit",),
    "migration": ("seer.export", "seer.import"),
    "dispatch": ("seer.dispatch",),
    "commit": ("seer.commit", "seer.commit_wait", TICK),
    "iteration": ("seer.iteration_open", "seer.iteration_close"),
}


def innermost(spans: Sequence[Span], lo: float, hi: float
              ) -> List[Tuple[float, float, Optional[str]]]:
    """Pieces ``(start, end, name)`` that tile ``[lo, hi]``: ``name`` is
    the innermost span covering the piece (the latest to start, the
    shortest on a tie), None where no span covers it."""
    spans = sorted((s, e, n) for n, s, e in spans if e > s)
    pts = sorted({lo, hi} | {min(max(t, lo), hi)
                             for s, e, _ in spans for t in (s, e)})
    out: List[Tuple[float, float, Optional[str]]] = []
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(pts, pts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        name = max(active, key=lambda sp: (sp[0], sp[0] - sp[1]))[2] \
            if active else None
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def charge(idle: Sequence[Tuple[float, float]],
           pieces: Sequence[Tuple[float, float, Optional[str]]]
           ) -> Dict[str, float]:
    """Seconds of the disjoint sorted ``idle`` intervals under each
    piece's name (None -> :data:`UNATTRIBUTED`)."""
    out: Dict[str, float] = {}
    j = 0
    for s, e in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            cover = min(b, e) - max(a, s)
            if cover > 0:
                key = name or UNATTRIBUTED
                out[key] = out.get(key, 0.0) + cover
            k += 1
    return out


def program(module_event: str) -> str:
    """``jit_seer_step_t64(1234)`` -> ``seer_step_t64``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def in_scope(path: str, scope: str) -> bool:
    """Whether a ``tf_op`` path (``jit(p)/while/body/attention/dot:``)
    runs under the named scope."""
    return scope in path.split("/")[1:-1]


def reduce_spans(device: Dict[str, List[Tuple[str, float, float]]],
                 modules: Dict[str, List[Span]], host: Sequence[Span],
                 lo: float, hi: float,
                 scope_of: Dict[str, str]) -> Optional[dict]:
    """``device``: chip -> its operations (name, start_s, end_s);
    ``modules``: chip -> its program runs; ``host``: host events; the
    window is ``[lo, hi]``; ``scope_of``: operation name -> ``tf_op``
    path.  Seconds per chip, averaged over the chips.  None when no
    ``seer.*`` span or no device operation was recorded."""
    phases = [h for h in host if h[0].startswith(PHASE_PREFIX)]
    if not phases or not any(device.values()):
        return None
    pieces = innermost(phases, lo, hi)
    n = len(device)
    idle: Dict[str, float] = {}
    prog: Dict[str, float] = {}
    attention = step_busy = idle_total = 0.0
    for chip, ops in device.items():
        cover = union((s, e) for _, s, e in ops)
        chip_idle = gaps(cover, lo, hi)
        idle_total += sum(e - s for s, e in chip_idle) / n
        for k, v in charge(chip_idle, pieces).items():
            idle[k] = idle.get(k, 0.0) + v / n
        runs: Dict[str, list] = {}
        for name, s, e in modules.get(chip, []):
            runs.setdefault(program(name), []).append((s, e))
        for p, iv in runs.items():
            prog[p] = prog.get(p, 0.0) + busy_seconds(union(iv), lo, hi) / n
        step_busy += busy_seconds(union(
            iv for p, ivs in runs.items() if p.startswith(STEP_PROGRAMS)
            for iv in ivs), lo, hi) / n
        attention += busy_seconds(union(
            (s, e) for name, s, e in ops
            if in_scope(scope_of.get(name, ""), "attention")), lo, hi) / n
    host_self: Dict[str, float] = {}
    for a, b, name in pieces:
        if name is not None:
            host_self[name] = host_self.get(name, 0.0) + (b - a)
    return {"idle_s": idle, "idle_total_s": idle_total,
            "host_self_s": host_self, "program_busy_s": prog,
            "step_busy_s": step_busy, "attention_busy_s": attention,
            "device_lead_s": device_lead(modules, host)}


def device_lead(modules: Dict[str, List[Span]], host: Sequence[Span]
                ) -> Optional[Tuple[float, float]]:
    """Bounds on how far the device's clock leads the host's in the
    trace, from each step program run.  The first ``seer.commit_wait``
    to end after the run ended is the wait for it; the last
    ``seer.dispatch`` to start before that wait began launched it, with
    the last runtime launch (:data:`LAUNCH`) inside that dispatch, or at
    its start where the trace holds none.  The run truly began after its
    launch began and ended before its wait ended, so the lead is at
    least the launch's start less the run's and at most the wait's end
    less the run's.  (largest lower bound, smallest upper bound) over
    the runs; exact for one instance on one chip, where each tick
    dispatches and waits for one step."""
    waits = sorted((e, s) for n, s, e in host if n == "seer.commit_wait")
    dispatches = sorted((s, e) for n, s, e in host if n == "seer.dispatch")
    launches = sorted(s for n, s, _ in host if n == LAUNCH)
    lower, upper = [], []
    for runs in modules.values():
        for name, rs, re in runs:
            k = bisect.bisect_left(waits, (re,))
            if not program(name).startswith(STEP_PROGRAMS) \
                    or k == len(waits):
                continue
            w_end, w_start = waits[k]
            upper.append(w_end - re)
            d = bisect.bisect_right(dispatches, (w_start,)) - 1
            if d < 0:
                continue
            d_start, d_end = dispatches[d]
            j = bisect.bisect_right(launches, d_end) - 1
            launch = launches[j] if j >= 0 and launches[j] >= d_start \
                else d_start
            lower.append(launch - rs)
    if not upper or not lower:
        return None
    return max(lower), min(upper)


# -- reading the trace ----------------------------------------------------


def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a protobuf message: ints for varints,
    bytes views for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_scopes(raw: bytes) -> Dict[str, str]:
    """Operation name -> ``tf_op`` path, from the device planes' event
    metadata of a serialized ``XSpace`` (tsl ``xplane.proto``: XSpace.
    planes = 1; XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1,
    str_value = 5, ref_value = 7; XStatMetadata.name = 2)."""
    out: Dict[str, str] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
                if not name.startswith(DEVICE_PREFIX):
                    break           # fields come in order: skip the rest
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        for v in events:
            op, path = "", None
            # a map entry (key = 1, value = 2) of XEventMetadata
            for mf, mv in _fields(dict(_fields(v)).get(2, b"")):
                if mf == 2:
                    op = bytes(mv).decode()
                elif mf == 5:
                    st = dict(_fields(mv))
                    if stat_names.get(st.get(1)) == "tf_op":
                        path = stat_names.get(st[7], "") if 7 in st \
                            else bytes(st.get(5, b"")).decode()
            if path is not None:
                out[op] = path
    return out


def read_modules(path: str) -> Dict[str, List[Span]]:
    """chip -> program runs (name, start_s, end_s) on its ``XLA Modules``
    line."""
    from jax.profiler import ProfileData
    out: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        runs = out.setdefault(plane.name, [])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                runs += [(ev.name, ev.start_ns * 1e-9,
                          (ev.start_ns + ev.duration_ns) * 1e-9)
                         for ev in line.events]
    return out


def reduce_file(path: str) -> Optional[dict]:
    device, host, lo, hi = read_xspace(path)
    with open(path, "rb") as f:
        scope_of = op_scopes(f.read())
    return reduce_spans(device, read_modules(path), host, lo, hi, scope_of)


# -- per engine step -------------------------------------------------------


def per_step(red: dict, engine_steps: int) -> dict:
    """The reduction in milliseconds per engine step: idle under each
    of :data:`GROUPS` and :data:`UNATTRIBUTED` (``<group>_idle_ms``), the
    host self time of each phase, device time of each program, and the
    attention scope's share of the step programs' device time (%)."""
    ms = 1e3 / max(engine_steps, 1)
    idle = red["idle_s"]
    out = {f"{g}_idle_ms": ms * sum(idle.get(p, 0.0) for p in names)
           for g, names in GROUPS.items()}
    out["unattributed_idle_ms"] = ms * idle.get(UNATTRIBUTED, 0.0)
    out["idle_ms"] = ms * red["idle_total_s"]
    out["host_self_ms"] = {k: ms * v for k, v in
                           sorted(red["host_self_s"].items())}
    out["program_device_ms"] = {k: ms * v for k, v in
                                sorted(red["program_busy_s"].items())}
    out["attention_busy_share"] = (
        100.0 * red["attention_busy_s"] / red["step_busy_s"]
        if red["step_busy_s"] else None)
    lead = red["device_lead_s"]
    out["device_lead_ms"] = None if lead is None \
        else [1e3 * lead[0], 1e3 * lead[1]]     # [at least, at most]
    return out

