#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json ``command``):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on the machine that holds the cell's
chips. The last line of standard output is the result as one JSON
object. Without an accelerator, with fewer chips than the cell asks
for, or without the program beside it, it exits non-zero and prints no
result. ``BENCH_RUN`` in the environment is ignored."""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import sys                         # noqa: E402
from pathlib import Path           # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmark import harness
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            root=ROOT, t_start=T_START)
    except harness.Refused as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for name, (number, limit) in result["compared"].items():
        print(f"compared {name}: {number} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
