"""Pallas fused Montgomery multiply vs the XLA-op reference — interpreter
mode (pallas_guide.md `interpret=True`; the TPU lowering shares the same
program)."""

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto.constants import P
from lighthouse_tpu.crypto.tpu import fp
from lighthouse_tpu.crypto.tpu.pallas_fp import TILE, mont_mul_pallas


def _rand_batch(n, seed):
    rng = random.Random(seed)
    return [rng.randrange(0, P) for _ in range(n)]


@pytest.mark.parametrize("n", [1, 7, TILE, TILE + 5])
def test_pallas_mont_mul_matches_reference(n):
    import jax.numpy as jnp

    xs = _rand_batch(n, seed=n)
    ys = _rand_batch(n, seed=n + 1)
    a = fp.to_mont(jnp.asarray(fp.ints_to_array(xs)))
    b = fp.to_mont(jnp.asarray(fp.ints_to_array(ys)))
    want = np.asarray(fp.mont_mul(a, b))
    got = np.asarray(mont_mul_pallas(a, b, interpret=True))
    assert np.array_equal(want, got)
    # and the value is the true product
    outs = fp.array_to_ints(np.asarray(fp.from_mont(jnp.asarray(got))))
    for x, y, o in zip(xs, ys, outs):
        # from_mont is lazily reduced: compare residues, not raw ints
        assert o % P == (x * y) % P


def test_pallas_handles_edge_values():
    import jax.numpy as jnp

    xs = [0, 1, P - 1, P - 2]
    a = fp.to_mont(jnp.asarray(fp.ints_to_array(xs)))
    b = fp.to_mont(jnp.asarray(fp.ints_to_array(list(reversed(xs)))))
    want = np.asarray(fp.mont_mul(a, b))
    got = np.asarray(mont_mul_pallas(a, b, interpret=True))
    assert np.array_equal(want, got)


def test_mont_chain_pallas_matches_xla():
    """The fused chain kernel (state in VMEM across iterations) is
    bit-identical to the XLA op-per-step chain (the byte-wall experiment
    machinery must be trustworthy before its ratio means anything)."""
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.tpu.pallas_fp import (
        mont_chain_pallas,
        mont_chain_xla,
    )

    vals_a = [pow(5, i + 1, P) for i in range(8)]
    vals_b = [pow(7, i + 1, P) for i in range(8)]
    a = fp.to_mont_jit(jnp.asarray(fp.ints_to_array(vals_a)))
    b = fp.to_mont_jit(jnp.asarray(fp.ints_to_array(vals_b)))
    got = mont_chain_pallas(a, b, steps=5, interpret=True)
    want = mont_chain_xla(a, b, steps=5)
    # same VALUE mod p (lazy representations may differ limb-wise only
    # if the pipelines diverge — they must not: compare canonically)
    got_ints = [x % P for x in fp.array_to_ints(np.asarray(got))]
    want_ints = [x % P for x in fp.array_to_ints(np.asarray(want))]
    assert got_ints == want_ints
