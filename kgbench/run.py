"""Run one benchmark cell once and print its result as the last line.

  python3 kgbench/run.py --workload lubm-zipf-open --seed 7 --seconds 30 \
      --trace 0

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from BENCHMARK.json at the root of the checkout. With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and from the server's own spans and counters. Lines on standard
error tell what the run did; the last of them are the numbers compared with
the reference, each with its limit. Without a TPU holding the cell's chips
the run exits with code 2 and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from kgbench import harness
    cell = harness.resolve(args.workload, ROOT)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             trace=bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
