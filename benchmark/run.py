#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this machine holds.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <window> --trace <0|1>

The cell names a configuration (configs/<name>.json) and a traffic mix
(traffic/<name>.json) in BENCHMARK.json at the root of the checkout.
The last line of standard output is one JSON object: correct, attempted,
failed, metrics (the cell's end-to-end metrics, or with --trace 1 its
per-layer metrics), device, and with --trace 1 a breakdown of the
traced window; its last key, `checks`, holds every number the
correctness check compared with its limit, and the last lines of
standard error repeat them.  Earlier lines are progress records.

Exit codes: 0 with a result; 1 when JAX's device is not a TPU or there
are fewer chips than the cell asks for, or the run cannot measure; 2
when the lighthouse_tpu package is not in the checkout; 3 when the run
outlives its deadline.  JAX's compilation caches live in
benchmark/.cache/ inside the checkout.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    for path in (ROOT, BENCH_DIR):
        if path not in sys.path:
            sys.path.insert(0, path)
    from harness import env

    env.setup(BENCH_DIR)             # before JAX is imported
    try:
        import lighthouse_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the lighthouse_tpu package is not here ({e})",
              file=sys.stderr)
        return 2
    from harness import cells, session

    try:
        cell = cells.load(BENCH_DIR, args.workload)
    except cells.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    return session.run(cell, args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
