#!/usr/bin/env python
"""Compile-tax bench: cold XLA compile vs cached AOT warm start.

For each canonical shape (default: the ShapePlanner prewarm menu) this
measures, on the current platform:

  * ``prewarm_cold_s``   — wall seconds for `compile_cache.prewarm` over
    the shape against an EMPTY cache directory (every program pays a
    full XLA compile — the old per-restart tax);
  * ``prewarm_cached_s`` — wall seconds for the same prewarm in a FRESH
    PROCESS against the now-populated directory (pure executable
    deserialization — the new restart cost);
  * ``cache_hit_rate``   — fraction of programs the cached start loaded
    without compiling (must be 1.0 for a usable cache);
  * ``warm_start_speedup`` — cold / cached.

Usage:
    python tools/compile_bench.py [--shapes 2x1,2x2] [--cache-dir D]
                                  [--json out.json]

The cached measurement runs in a subprocess (``--load-only`` mode) so it
is an honest second-process start, not an in-process re-load.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_shapes(raw):
    out = []
    for part in raw.split(","):
        n, m = part.lower().strip().split("x")
        out.append((int(n), int(m)))
    return out


def run_prewarm(shapes, cache_dir):
    """In-process prewarm over `shapes` against `cache_dir`; returns the
    prewarm summary dict (wall_s, cache_{hits,misses,hit_rate})."""
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    cache = cc.CompileCache(cache_dir=cache_dir, enabled=True)
    return cc.prewarm(shapes=shapes, cache=cache)


def cached_start_subprocess(shapes, cache_dir, timeout=1800):
    """Measure a SECOND-process prewarm against a populated cache dir
    (CPU only: the child is pinned there)."""
    spec = ",".join(f"{n}x{m}" for n, m in shapes)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--load-only", "--shapes", spec, "--cache-dir", cache_dir],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"load-only subprocess failed rc={out.returncode}: "
            f"{out.stderr[-400:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_shapes(shapes, cache_dir=None, subprocess_load=True):
    """The full cold-vs-cached measurement.  Returns a summary dict with
    per-shape detail and aggregate keys for the BENCH artifacts."""
    own_dir = cache_dir is None
    if own_dir:
        cache_dir = tempfile.mkdtemp(prefix="ltpu-compile-bench-")
    detail = []
    try:
        t0 = time.time()
        cold = run_prewarm(shapes, cache_dir)
        cold_s = round(time.time() - t0, 3)
        if subprocess_load:
            cached = cached_start_subprocess(shapes, cache_dir)
        else:
            # in-process fallback (tests): a fresh CompileCache instance
            # against the same dir — same deserialization work
            cached = run_prewarm(shapes, cache_dir)
        cached_s = cached["wall_s"]
        hit_rate = cached["cache_hit_rate"]
        for c in cold.get("programs_detail", []):
            detail.append(dict(c, phase="cold"))
        for c in cached.get("programs_detail", []):
            detail.append(dict(c, phase="cached"))
        return {
            "shapes": [f"{n}x{m}" for n, m in shapes],
            "programs": cold["programs"],
            "prewarm_cold_s": cold_s,
            "prewarm_cached_s": cached_s,
            "cache_hit_rate": hit_rate,
            "warm_start_speedup": (
                round(cold_s / cached_s, 2) if cached_s > 0 else None
            ),
            "cached_within_25pct_of_cold": (
                cached_s <= 0.25 * cold_s if cold_s > 0 else True
            ),
            "programs_detail": detail,
        }
    finally:
        if own_dir:
            shutil.rmtree(cache_dir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shapes", default=None,
                    help="comma-separated NxM canonical shapes "
                         "(default: the planner prewarm menu)")
    ap.add_argument("--cache-dir", default=None,
                    help="cache directory (default: fresh tmp dir, "
                         "removed afterwards)")
    ap.add_argument("--json", default=None, help="also write summary here")
    ap.add_argument("--load-only", action="store_true",
                    help="internal: prewarm against an existing cache "
                         "dir and print the summary (the second-process "
                         "measurement)")
    args = ap.parse_args()

    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    shapes = (_parse_shapes(args.shapes) if args.shapes
              else list(cc.get_planner().prewarm_menu))

    if args.load_only:
        summary = run_prewarm(shapes, args.cache_dir)
        print(json.dumps(summary))
        return 0

    import jax

    # a child cannot reach a chip this process holds: off the CPU the
    # cached start runs in-process against a fresh CompileCache
    summary = bench_shapes(
        shapes, cache_dir=args.cache_dir,
        subprocess_load=jax.devices()[0].platform == "cpu",
    )
    line = json.dumps(summary)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
