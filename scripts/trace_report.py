"""Tail-latency attribution report from a rollout flight-recorder trace.

Reads a Chrome trace-event JSON file produced by ``Tracer.to_chrome``
(engine or simulator tier — both emit the same schema), rebuilds the
per-request phase timelines and prints the tail-attribution table:
wall-time percentiles, per-phase totals, and the phase decomposition of
the p99 / p999 / slowest-10% cohorts versus the full population.

Seconds are the trace's own: an engine trace reports wall seconds (the
JAX profiler's host clock, counted from the tracer's start), a
simulator trace modeled seconds.

Usage::

    PYTHONPATH=src python scripts/trace_report.py trace.json
    PYTHONPATH=src python scripts/trace_report.py --demo [--out trace.json]

``--demo`` runs a small seeded divided-rollout simulation with faults
and reports on its trace (writing the Chrome JSON to ``--out`` when
given) — useful for eyeballing the report format without an engine run.
"""
from __future__ import annotations

import argparse
import json
import sys


def _demo_events(seed: int) -> list:
    import dataclasses

    from repro.configs import get_config
    from repro.core.simulator import ClusterSimulator, SimConfig
    from repro.data.workload import MOONLIGHT, make_workload
    from repro.obs import Tracer

    spec = dataclasses.replace(MOONLIGHT, n_requests=48, group_size=4,
                               n_instances=2, max_gen_length=8192,
                               mean_gen_length=2000)
    tr = Tracer()
    sim = ClusterSimulator(
        get_config("yi-6b"), spec,
        SimConfig(mode="divided", policy="seer", max_slots=16,
                  chips_per_instance=1, kv_capacity_tokens=40_000,
                  chunk_size=512, fault_rate=0.02, seed=seed),
        tracer=tr)
    sim.run(make_workload(spec, seed=seed))
    return tr.events(), tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", default=None,
                    help="Chrome trace-event JSON file (Tracer.to_chrome)")
    ap.add_argument("--demo", action="store_true",
                    help="run a seeded fault-injected simulation instead "
                         "of reading a trace file")
    ap.add_argument("--out", default=None,
                    help="with --demo: also write the demo trace's "
                         "Chrome JSON here")
    ap.add_argument("--seed", type=int, default=3,
                    help="demo simulation seed")
    args = ap.parse_args(argv)

    from repro.obs import Tracer, format_attribution, tail_attribution, \
        timelines_from_events

    if args.demo:
        events, tracer = _demo_events(args.seed)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(tracer.to_chrome(), f)
            print(f"[trace_report] wrote {len(events)} events to "
                  f"{args.out}")
    elif args.trace:
        with open(args.trace) as f:
            events = Tracer.from_chrome(json.load(f))
    else:
        ap.error("give a trace file or --demo")

    timelines = timelines_from_events(events)
    if not timelines:
        print("[trace_report] no request timelines in trace "
              f"({len(events)} events)")
        return 1
    report = tail_attribution(timelines)
    print(format_attribution(report))
    if not report["conserved"]:
        print("[trace_report] WARNING: span conservation violated — "
              "some request's phase spans do not tile its wall interval")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
