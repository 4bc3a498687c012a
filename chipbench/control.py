"""Run the float8 control in the program's place, on the chip, for
setting the comparison's limit: for each seed, one run of the cell scores
the program's served tokens against the float32 reference (the line's
``program_gap``) and, at the same positions, holds the control's first
choices to the ``max_logit_gap`` check, which must come out not correct.
All seeds run in one process, so programs compile once.

  python3 chipbench/control.py --workload <cell> --seconds 10 \
      --seeds 11 12 13

Each seed's result line goes to standard output.  The benchmark's own
runs never run the control.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import harness

    def log(msg):
        print(f"[{time.monotonic() - T_START:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    c = harness.cell(args.workload)
    for seed in args.seeds:
        line = harness.measure(c, seed, args.seconds, False,
                               time.monotonic(), control=True, log=log)
        line["seed"] = seed
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
