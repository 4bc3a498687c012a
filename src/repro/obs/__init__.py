"""Flight-recorder observability for the Seer rollout stack.

``repro.obs`` is a zero-extra-host-sync tracing layer: every event is
host-side metadata recorded at stream-loop tick boundaries or around
the tick's host phases (the same no-step-ticket-in-flight contract as
``inject()``/``refresh_params()`` for everything but the phases), so
tracing never adds a device read and a traced run is bit-identical —
tokens, steps, host syncs — to an untraced one.

* :mod:`repro.obs.trace` — the :class:`~repro.obs.trace.Tracer`
  (span/instant events stamped in ticks and in seconds: wall seconds
  on the JAX profiler's host clock in the engine tier, explicit
  modeled seconds in the simulator tier; ticks and
  :meth:`~repro.obs.trace.Tracer.phase` spans mirrored as profiler
  annotations; Chrome trace-event JSON export).
* :mod:`repro.obs.timeline` — per-request phase timelines
  (:class:`~repro.obs.timeline.RequestTimeline`), the tick-boundary
  :class:`~repro.obs.timeline.TimelineRecorder`, and the
  tail-latency attribution report.
"""
from repro.obs.trace import TraceEvent, Tracer
from repro.obs.timeline import (PHASES, RequestTimeline, TimelineRecorder,
                                format_attribution, tail_attribution,
                                timelines_from_events)

__all__ = [
    "TraceEvent", "Tracer", "PHASES", "RequestTimeline",
    "TimelineRecorder", "tail_attribution", "timelines_from_events",
    "format_attribution",
]
