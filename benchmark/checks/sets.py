#!/usr/bin/env python3
"""Run one cell once per seed, one process after another, and report
the spread of each metric.

    python3 benchmark/checks/sets.py --workload <cell> --seeds 11,12,13 \
        [--seconds 50] [--trace 0] [--out runs.jsonl]

Each run is `python3 benchmark/run.py ...` as the benchmark's command
runs it; its exit code, wall time, result line and the end of its
standard error are appended to --out as one JSON line.  The last line
printed holds, per metric, the values, the median and the spread
(interquartile distance over the median, statistics.quantiles n=4).
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    seconds = a.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    values = {}
    for seed in [int(s) for s in a.seeds.split(",") if s]:
        cmd = [sys.executable, "benchmark/run.py", "--workload", a.workload,
               "--seed", str(seed), "--seconds", str(seconds),
               "--trace", str(a.trace)]
        t0 = time.monotonic()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.monotonic() - t0
        lines = p.stdout.strip().splitlines()
        result = None
        if p.returncode == 0 and lines:
            result = json.loads(lines[-1])
            for k, v in result["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        rec = {"workload": a.workload, "seed": seed, "trace": a.trace,
               "rc": p.returncode, "wall_s": wall,
               "progress": lines[:-1] if result else lines,
               "result": result, "stderr_tail": p.stderr[-3000:]}
        if a.out:
            os.makedirs(os.path.dirname(os.path.abspath(a.out)),
                        exist_ok=True)
            with open(a.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        brief = None if result is None else {
            "correct": result["correct"], "failed": result["failed"],
            **{k: v["value"] for k, v in result["metrics"].items()}}
        print(json.dumps({"seed": seed, "rc": p.returncode,
                          "wall_s": round(wall, 1), "result": brief}),
              flush=True)
        if p.returncode != 0:
            print(p.stderr[-1500:], flush=True)
    summary = {}
    for k, vals in values.items():
        entry = {"values": vals, "median": statistics.median(vals)}
        if len(vals) >= 2:
            q1, med, q3 = statistics.quantiles(vals, n=4)
            entry["spread"] = (q3 - q1) / med if med else None
        summary[k] = entry
    print(json.dumps({"workload": a.workload, "summary": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
