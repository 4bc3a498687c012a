"""Run one cell of the chip benchmark once.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  Progress goes to
standard error, ending with each number that decides ``correct`` beside
its limit; the last line of standard output is one JSON object (keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, then ``window_compiles`` and
``checks``).  Without a chip of a kind in ``peaks.py`` (or with fewer
chips than the cell asks for) the run exits with 3 and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# JAX's persistent compilation cache: inside the checkout, at a fixed path
# (the path is part of the cache's key), whatever the environment names
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    def log(msg):
        print(f"[{time.monotonic() - T_START:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    try:
        line = harness.measure(harness.cell(args.workload), args.seed,
                               args.seconds, bool(args.trace), T_START,
                               log=log)
    except harness.NoChip as e:
        log(f"refused: {e}")
        return 3
    for name, c in line["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
