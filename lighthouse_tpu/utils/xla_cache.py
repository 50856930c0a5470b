"""Where the two compile caches live.

JAX's persistent compilation cache and the AOT executable cache
(crypto/tpu/compile_cache.py) follow one rule:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself, and the AOT
    cache lives in its `ltpu-aot/` subdirectory.  Nothing in the repo
    overrides it, so a directory placed from outside serves both tiers.
  * unset: fixed paths in the checkout — `<repo>/.xla_cache/<cpu-fp>`
    for JAX's cache and `<repo>/.compile_cache/` for the AOT cache.  A
    cache path is part of the cache's key, so it is never temporary,
    pid- or time-based.

XLA:CPU AOT artifacts are machine-feature-specific: loading an entry
compiled on a different CPU generation logs feature-mismatch errors and
risks SIGILL.  Keying the in-checkout JAX directory by a fingerprint of
the host's CPU features makes a foreign cache invisible instead of a
hazard (the AOT cache keys its entries by the same fingerprint).
"""

import hashlib
import os
import platform

ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cpu_fingerprint() -> str:
    bits = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    bits.append(line.strip())
                    break
                if line.startswith("model name"):
                    bits.append(line.strip())
    except OSError:
        bits.append(platform.processor() or "unknown")
    return hashlib.sha256("|".join(bits).encode()).hexdigest()[:12]


def cache_dir() -> str:
    """JAX's persistent cache: $JAX_COMPILATION_CACHE_DIR, or
    <repo>/.xla_cache/<cpu-fingerprint>."""
    return os.environ.get(ENV) or os.path.join(
        _REPO_ROOT, ".xla_cache", _cpu_fingerprint()
    )


def aot_cache_dir() -> str:
    """The AOT executable cache: $JAX_COMPILATION_CACHE_DIR/ltpu-aot, or
    <repo>/.compile_cache."""
    env = os.environ.get(ENV)
    if env:
        return os.path.join(env, "ltpu-aot")
    return os.path.join(_REPO_ROOT, ".compile_cache")


def configure():
    """Turn on JAX's persistent cache for this process.  The directory
    is set only when the environment leaves it unset — JAX already took
    $JAX_COMPILATION_CACHE_DIR at import."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
