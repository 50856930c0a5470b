"""The command refuses a machine without a TPU, and a checkout without
the program, with no result line."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH_DIR, ROOT

ARGS = ["--workload", "att_gossip_1m.saturate", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py"] + ARGS, cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_a_cpu_is_refused():
    p = _run(ROOT)
    assert p.returncode == 1, p.stderr[-2000:]
    assert "not a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_a_checkout_without_the_program_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
