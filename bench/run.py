#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run it from the root of a checkout.  The cell is an entry of
``BENCHMARK.json``; see ``bench/harness.py`` for what a run does.  The last
line of standard output is one JSON object; the compared numbers, each with
its limit, are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits with code 2 and prints no
result; a broken benchmark file or run exits with code 1.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    harness.use_checkout_cache(ROOT)
    try:
        cell = harness.load_cell(args.workload, ROOT)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    except harness.BenchError as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
