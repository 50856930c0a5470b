#!/usr/bin/env python3
"""The correctness check's two readings for one cell, in one process.

    python3 benchmark/checks/control.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 21,22,23 [--seconds 50] \
        [--out control.jsonl]

Sound runs: the cell's programs are loaded once; then, per seed, the
seed's data, the warm-up and a window of the cell's loop at its own load
through the service over the device, compared with the reference as a
run compares it (`wrong_verdicts`, `missing_verdicts`).

Control: the reference with one guarantee broken, put in the program's
place: a set is accepted when its signature is a point of G2, with the
pairing check left out (the shortcut that would tempt a later change).
Per control seed, the same window runs over a service whose verifier is
the control; a closed loop sends as many requests as the first sound run
sent.  The control has to read `correct` false on every seed.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


class ControlVerifier:
    """Accepts every set whose signature lies in G2."""

    backend = "control"

    def __init__(self, g2_in_subgroup):
        self._in_g2 = g2_in_subgroup

    def verify_signature_sets_per_set(self, sets, priority=None):
        return [s.signature is not None and self._in_g2(s.signature)
                for s in sets]

    def verify_signature_sets(self, sets, priority=None):
        return all(self.verify_signature_sets_per_set(sets))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    sys.path[0:0] = [BENCH_DIR, ROOT]
    from harness import env

    env.setup(BENCH_DIR)

    from harness import cells, check, program, session, traffic
    from lighthouse_tpu.utils import xla_cache
    from lighthouse_tpu.verify_service import VerificationService
    from reference import bls12_381 as B
    from reference import pool as message_pool
    from reference.verdicts import Reference

    cell = cells.load(BENCH_DIR, a.workload)
    if program.devices()[0].platform != "tpu":
        print("control: JAX's default device is not a TPU", file=sys.stderr)
        return 1
    seconds = a.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    tr = cell.traffic
    program.set_program_env(cell.config, os.environ)
    xla_cache.configure()
    compiles = program.Compiles()
    pool = message_pool.load()
    ref = Reference(pool)
    per_set = bool(tr["want_per_set"])

    def plan_of(seed):
        return traffic.build(cell, seed, pool, program.bucket(),
                             program.signature_set)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(line + "\n")

    seeds = [int(s) for s in a.seeds.split(",") if s]
    control_seeds = [int(s) for s in a.control_seeds.split(",") if s]
    loaded = None
    sent = None
    for kind, seed in ([("program", s) for s in seeds]
                       + [("control", s) for s in control_seeds]):
        plan = plan_of(seed)
        if kind == "program":
            if loaded is None:
                loaded = program.load_programs(
                    compiles, program.pk_width(plan.window[0].sets),
                    per_set="per_set" in tr["programs"])
            svc = program.service()
            limit = None
        else:
            svc = VerificationService(ControlVerifier(B.g2_in_subgroup),
                                      host_verifier=program.NoHost())
            limit = sent
        session.warm_up(svc, plan, tr)
        t0 = time.monotonic()
        records = session.drive(cell, svc, plan.window, t0, seconds,
                                limit=limit)
        svc.stop()
        if kind == "program" and sent is None:
            sent = len(records)
        ok, numbers, compared = check.compare(records, ref, per_set)
        emit({"workload": a.workload, "kind": kind, "seed": seed,
              "correct": ok, "compared": compared,
              "invalid_sets": sum(len(r.request.invalid) for r in records
                                  if r.answered),
              "errors": sum(r.error is not None for r in records),
              "window_s": time.monotonic() - t0, **numbers})
    return 0


if __name__ == "__main__":
    sys.exit(main())
