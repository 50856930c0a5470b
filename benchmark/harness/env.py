"""The process environment every benchmark entry point sets before JAX
is imported: JAX's persistent compilation cache (and the program's
executable cache under it) at a fixed path inside the checkout, and the
TPU runtime's log files off."""

import os


def setup(bench_dir):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        bench_dir, ".cache", "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
