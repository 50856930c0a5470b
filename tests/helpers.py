"""Shared test utilities: cached jit wrappers.

Eager per-op dispatch is slow, so tests run every kernel under `jax.jit`.
The cache keys on the function object so repeated calls reuse the compiled
executable.
"""

import jax

_cache = {}


def J(fn, **jit_kwargs):
    key = (fn, tuple(sorted(jit_kwargs.items())))
    if key not in _cache:
        _cache[key] = jax.jit(fn, **jit_kwargs)
    return _cache[key]
