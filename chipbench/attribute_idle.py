"""One traced run of a cell with a ``repro.obs`` Tracer attached to the
rollout, and where the device's idle time goes in it.

  python3 chipbench/attribute_idle.py --workload <cell> --seed <n> \
      --seconds <s>

The run is ``run.py --trace 1``'s (``harness.measure``) with two of its
steps wrapped: the traffic kind's ``window`` attaches a ``Tracer`` to
the rollout when the window opens (after warm-up), and the trace is also
reduced by ``span_reduce`` before the harness removes it.  The tracer
adds its host work to the window, so the run's end-to-end numbers are
those of a traced program, not of the benchmark's untraced runs.

Standard error: the harness's progress, then per engine step each
phase's host self time and the device idle time charged to it, and the
device time of each program.  The last line of standard output is one
JSON object: the harness's result line under ``result`` and the
per-step reduction (``span_reduce.per_step``, with
``column_occupancy``) under ``attribution`` (null where the trace has no
device plane or no ``seer.*`` span).
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402


def columns(ro):
    return (sum(i.cols_active for i in ro.instances),
            sum(i.cols_total for i in ro.instances))


def attribute(c, seed: int, seconds: float, t_start: float, *,
              require_chip: bool = True, log=print):
    """(the JSON object described above, the tracer)."""
    from chipbench import harness, span_reduce
    from repro.obs import Tracer

    tracer, seen = Tracer(), {}
    load_kind, reduce_trace = harness.load_kind, harness.reduce_trace

    def traced_kind(kind):
        mod = load_kind(kind)

        def window(ro, c, seed, vocab, deadline, readings, log):
            seen["readings"] = readings
            ro.tracer = tracer
            before = columns(ro)
            try:
                return mod.window(ro, c, seed, vocab, deadline, readings,
                                  log)
            finally:
                ro.tracer = None
                seen["columns"] = [b - a for a, b in
                                   zip(before, columns(ro))]
        return SimpleNamespace(warm=mod.warm, window=window)

    def reduce_both():
        found = sorted(harness.TRACE_DIR.rglob("*.xplane.pb"))
        seen["spans"] = span_reduce.reduce_file(str(found[-1])) \
            if found else None
        return reduce_trace()

    harness.load_kind, harness.reduce_trace = traced_kind, reduce_both
    try:
        line = harness.measure(c, seed, seconds, True, t_start,
                               require_chip=require_chip, log=log)
    finally:
        harness.load_kind, harness.reduce_trace = load_kind, reduce_trace
    att = None
    if seen.get("spans"):
        steps = seen["readings"]["engine_steps"]
        att = span_reduce.per_step(seen["spans"], steps)
        active, total = seen["columns"]
        att["column_occupancy"] = 100.0 * active / total if total else None
        att["engine_steps"] = steps
        report(att, log)
    return {"result": line, "attribution": att}, tracer


def report(att: dict, log) -> None:
    from chipbench.span_reduce import GROUPS
    for g in list(GROUPS) + ["unattributed"]:
        log(f"idle {g}: {att[f'{g}_idle_ms']:.3f} ms/step")
    log(f"idle total: {att['idle_ms']:.3f} ms/step over "
        f"{att['engine_steps']} steps")
    for name, ms in att["host_self_ms"].items():
        log(f"host self {name}: {ms:.3f} ms/step")
    for name, ms in att["program_device_ms"].items():
        log(f"device {name}: {ms:.3f} ms/step")
    log(f"column_occupancy {att['column_occupancy']}, "
        f"attention_busy_share {att['attention_busy_share']}, "
        f"device clock lead (at least, at most) ms {att['device_lead_ms']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import harness

    def log(msg):
        print(f"[{time.monotonic() - T_START:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    try:
        out, _ = attribute(harness.cell(args.workload), args.seed,
                           args.seconds, T_START, log=log)
    except harness.NoChip as e:
        log(f"refused: {e}")
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
    # run.py's paths and compile cache, as a run of the cell has them
    from chipbench import run  # noqa: F401
    sys.exit(main())
