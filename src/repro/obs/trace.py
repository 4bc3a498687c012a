"""Tick-boundary event tracer for the rollout engine and the simulator.

Design constraints (the whole reason this module exists as its own
layer instead of ``print`` calls):

* **Zero extra host syncs.**  Every value an event carries is host-side
  metadata the stream loop already holds (slot counts, req ids).  No
  hook may touch a jax array — the engine's 1-host-sync-per-step
  contract is enforced by transfer-guard tests with a tracer attached.
* **Ticks, and one clock per tier.**  Every event is stamped in
  stream-loop *ticks* and in seconds.  The engine tier's seconds are
  *wall* seconds since the tracer's :attr:`Tracer.origin_ns`, read from
  the clock the JAX profiler stamps its host events with
  (``CLOCK_REALTIME``, :func:`time.time_ns`): :meth:`Tracer.begin_tick`
  stamps each tick's start, request spans resolve their tick bounds
  through that table, and instants and :meth:`Tracer.phase` spans are
  stamped when they happen.  The simulator tier passes explicit
  *modeled* ``t0``/``t1`` from its
  :class:`~repro.core.sdmodel.ForwardCostModel`.  Ticks, names and
  args are a pure function of (seed, config) in both tiers; engine
  seconds are not.
* **Mirrored into the profiler.**  Each tick is a
  ``jax.profiler.StepTraceAnnotation("seer.tick")`` and each
  :meth:`Tracer.phase` span a ``jax.profiler.TraceAnnotation`` of the
  span's name, so a profiler trace taken meanwhile holds them on its
  ``/host:CPU`` plane, on the same clock as the device's operations: a
  stamp ``t`` here is the profiler's ``profile_start_time + start_ns``
  at ``origin_ns + t * 1e9``.  With no tracer attached nothing is
  stamped and no annotation is created.
* **One schema for engine and simulator.**  Both tiers emit the same
  :class:`TraceEvent` shape, so their traces are directly diffable.
"""
from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

#: Event categories — the fixed vocabulary both tiers emit (``phase``:
#: the engine tier's host phases of a tick, :meth:`Tracer.phase`).
CATEGORIES = ("request", "instance", "scheduler", "pool", "fault",
              "feed", "train", "phase")

#: Keys every serialized event carries (the cross-tier schema).
SCHEMA_KEYS = ("name", "cat", "ph", "track", "tick0", "tick1",
               "t0", "t1", "args")

#: Name of the profiler's step annotation around each engine tick.
TICK_EVENT = "seer.tick"

#: What a call site opens in place of :meth:`Tracer.phase` when no
#: tracer is attached: ``with tr.phase(...) if tr is not None else
#: NO_SPAN:`` creates no span and no annotation.
NO_SPAN = nullcontext()


@dataclass
class TraceEvent:
    """One recorded event.

    ``ph`` follows the Chrome trace-event phase vocabulary: ``"X"`` is a
    complete span over ``[tick0, tick1)``, ``"i"`` an instant at
    ``tick0``.  ``t0``/``t1`` are seconds: wall seconds since the
    tracer's origin in the engine tier, modeled seconds in the
    simulator tier.
    """

    name: str
    cat: str
    ph: str
    track: str
    tick0: int
    tick1: int
    t0: float = 0.0
    t1: float = 0.0
    args: dict = field(default_factory=dict)


class Tracer:
    """Append-only event recorder on the profiler's host clock.

    The stream loop calls :meth:`begin_tick` at each tick boundary and
    :meth:`end_tick` at the tick's end; hooks anywhere in between stamp
    events with :attr:`cur_tick` implicitly, and :meth:`phase` brackets
    a host phase of the tick.  ``events()`` returns the serializable
    view; ``to_chrome()``/``from_chrome()`` round-trip Perfetto-loadable
    Chrome trace-event JSON.
    """

    def __init__(self) -> None:
        self._events: List[TraceEvent] = []
        #: ``time.time_ns()`` at second 0 of this tracer's stamps
        self.origin_ns: int = time.time_ns()
        # _tick_t[k] = wall seconds at the START of tick k of the
        # current stream; end_tick appends the tick's end, which the
        # next begin_tick replaces with its own start
        self._tick_t: List[float] = [0.0]
        self.cur_tick: int = 0
        self._tick_note = None       # the open seer.tick annotation

    def __len__(self) -> int:
        return len(self._events)

    # -- clock -------------------------------------------------------------

    def now(self) -> float:
        """Wall seconds since :attr:`origin_ns`."""
        return (time.time_ns() - self.origin_ns) * 1e-9

    def begin_tick(self, tick: int) -> None:
        """Tick boundary: stamp the tick's start and open its
        ``seer.tick`` step annotation; subsequent events default to this
        tick.  Tick 0 starts a new stream's table."""
        self._close_tick_note()
        tick = int(tick)
        self.cur_tick = tick
        del self._tick_t[tick:]
        self._tick_t.extend([self.now()] * (tick + 1 - len(self._tick_t)))
        self._tick_note = jax.profiler.StepTraceAnnotation(
            TICK_EVENT, step_num=tick)
        self._tick_note.__enter__()

    def end_tick(self) -> None:
        """End of tick: close its annotation and stamp its end (the
        table's last point, so spans ending after the last tick
        resolve)."""
        self._close_tick_note()
        self._tick_t.append(self.now())

    def _close_tick_note(self) -> None:
        if self._tick_note is not None:
            self._tick_note.__exit__(None, None, None)
            self._tick_note = None

    def tick_time(self, tick: int) -> float:
        """Wall seconds at the start of ``tick`` of the current stream
        (clamped to the recorded range, so late ticks saturate at the
        stream's end)."""
        i = min(max(int(tick), 0), len(self._tick_t) - 1)
        return self._tick_t[i]

    # -- recording ---------------------------------------------------------

    def instant(self, name: str, cat: str, track: str, *,
                tick: Optional[int] = None,
                t: Optional[float] = None, **args) -> None:
        """An instant at ``t`` (default: now)."""
        k = self.cur_tick if tick is None else int(tick)
        t = self.now() if t is None else t
        self._events.append(TraceEvent(
            name=name, cat=cat, ph="i", track=str(track),
            tick0=k, tick1=k, t0=t, t1=t, args=args))

    def span(self, name: str, cat: str, track: str,
             tick0: int, tick1: int, *,
             t0: Optional[float] = None, t1: Optional[float] = None,
             **args) -> None:
        """A span over ``[tick0, tick1)``; seconds default to those
        ticks' starts in the tick table."""
        self._events.append(TraceEvent(
            name=name, cat=cat, ph="X", track=str(track),
            tick0=int(tick0), tick1=int(tick1),
            t0=self.tick_time(tick0) if t0 is None else t0,
            t1=self.tick_time(tick1) if t1 is None else t1, args=args))

    @contextmanager
    def phase(self, name: str, track: str, **args):
        """A host phase: an ``"X"`` span (cat ``phase``) stamped in
        ticks and wall seconds from entry to exit, mirrored as a
        ``jax.profiler.TraceAnnotation`` of the same name.  Yields the
        event, whose ``args`` the body may extend."""
        with jax.profiler.TraceAnnotation(name):
            t = self.now()
            ev = TraceEvent(name=name, cat="phase", ph="X",
                            track=str(track), tick0=self.cur_tick,
                            tick1=self.cur_tick, t0=t, t1=t, args=args)
            self._events.append(ev)
            try:
                yield ev
            finally:
                ev.tick1 = self.cur_tick
                ev.t1 = self.now()

    # -- export ------------------------------------------------------------

    def events(self) -> List[dict]:
        """Serializable events (insertion order), each carrying exactly
        :data:`SCHEMA_KEYS`."""
        return [{"name": e.name, "cat": e.cat, "ph": e.ph,
                 "track": e.track, "tick0": e.tick0, "tick1": e.tick1,
                 "t0": e.t0, "t1": e.t1, "args": dict(e.args)}
                for e in self._events]

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable).

        Tracks map to threads of one process; seconds map to
        microsecond ``ts``.  The exact resolved event (ticks and float
        seconds) rides along in ``args`` so :meth:`from_chrome` is a
        lossless inverse of :meth:`events`.
        """
        tids: Dict[str, int] = {}
        trace_events = []
        for e in self.events():
            tid = tids.setdefault(e["track"], len(tids) + 1)
            args = dict(e["args"])
            args.update(track=e["track"], tick0=e["tick0"],
                        tick1=e["tick1"], t0=e["t0"], t1=e["t1"])
            ev = {"name": e["name"], "cat": e["cat"], "ph": e["ph"],
                  "pid": 1, "tid": tid,
                  "ts": e["t0"] * 1e6, "args": args}
            if e["ph"] == "X":
                ev["dur"] = max(e["t1"] - e["t0"], 0.0) * 1e6
            else:
                ev["s"] = "t"
            trace_events.append(ev)
        meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                 "args": {"name": track}}
                for track, tid in tids.items()]
        return {"traceEvents": meta + trace_events,
                "displayTimeUnit": "ms"}

    @staticmethod
    def from_chrome(obj: dict) -> List[dict]:
        """Rebuild the :meth:`events` view from Chrome JSON."""
        out = []
        for ev in obj.get("traceEvents", []):
            if ev.get("ph") == "M":
                continue
            args = dict(ev.get("args", {}))
            track = args.pop("track")
            tick0 = args.pop("tick0")
            tick1 = args.pop("tick1")
            t0 = args.pop("t0")
            t1 = args.pop("t1")
            out.append({
                "name": ev["name"], "cat": ev["cat"], "ph": ev["ph"],
                "track": track, "tick0": tick0, "tick1": tick1,
                "t0": t0, "t1": t1, "args": args,
            })
        return out


def schema_keys(events: List[dict]) -> List[str]:
    """Sorted union of top-level keys across ``events`` — the
    engine-vs-simulator schema-diff primitive."""
    keys = set()
    for e in events:
        keys.update(e.keys())
    return sorted(keys)
