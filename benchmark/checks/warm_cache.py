#!/usr/bin/env python3
"""Fill benchmark/.cache with the verify programs of every cell, in one
process, their compiles side by side.

    python3 benchmark/checks/warm_cache.py [cell ...]

With no cell named, every cell of BENCHMARK.json.  A cold checkout's
first run of each cell compiles that cell's programs (minutes each on a
v5e host); running this first lets every run of a measurement call
deserialize them instead.  It needs the chip, and
holds it until it exits.
"""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


def main():
    sys.path[0:0] = [BENCH_DIR, ROOT]
    from harness import env

    env.setup(BENCH_DIR)
    import json

    from harness import cells, program, traffic
    from lighthouse_tpu.crypto.tpu import bls
    from lighthouse_tpu.crypto.tpu import compile_cache as cc
    from lighthouse_tpu.utils import xla_cache
    from reference import pool as message_pool

    if program.devices()[0].platform != "tpu":
        print("warm_cache: JAX's default device is not a TPU",
              file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = sys.argv[1:] or [w["name"] for w in
                                 json.load(f)["workloads"]]
    pool = message_pool.load()
    specs, seen = [], set()
    for name in names:
        cell = cells.load(BENCH_DIR, name)
        program.set_program_env(cell.config, os.environ)
        plan = traffic.build(cell, 1, pool, program.bucket(),
                             program.signature_set)
        width = program.pk_width(plan.window[0].sets)
        per_set = "per_set" in cell.traffic["programs"]
        for spec in bls.kernel_specs(program.bucket(), width,
                                     per_set=per_set):
            if (spec[0], spec[3]) not in seen:
                seen.add((spec[0], spec[3]))
                specs.append(spec)
    xla_cache.configure()
    compiles = program.Compiles()
    t0 = time.monotonic()
    # widest first: the 32x512 program takes longest
    specs.sort(key=lambda s: -int(s[3].split("x")[1]))
    jobs = [(s[0], cc.load_programs, {"specs": [s]}) for s in specs]
    for t in compiles.start_in_order(jobs):
        t.join()
    loaded = cc.get_cache().stats()["loaded"]
    print(json.dumps({"warm_cache_s": time.monotonic() - t0,
                      "programs": loaded}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
