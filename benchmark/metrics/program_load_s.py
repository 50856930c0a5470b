"""Compile lifecycle (crypto/tpu/compile_cache.py): seconds the cell's
verify programs took to load, deserialized or compiled, from the
cache's stats()["loaded"]."""

def read(w):
    if not w.programs:
        return None
    return sum(p["ms"] for p in w.programs.values()) / 1e3
