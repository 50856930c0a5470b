"""Benchmark tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They stub the harness's look for a chip inside the test only; the
command itself still refuses a CPU (test_cli.py)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)
