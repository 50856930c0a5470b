"""The benchmark's own arithmetic against the program's pure-Python
oracle (a test may read the program; the reference never does)."""

import random

import pytest

from reference import bls12_381 as B
from reference import pool as message_pool
from reference.verdicts import Reference


@pytest.fixture(scope="module")
def pool():
    return message_pool.load()


def test_pool_points_are_hash_to_g2_of_their_messages(pool):
    from lighthouse_tpu.crypto.ref.hash_to_curve import hash_to_g2

    assert len(pool) == 1024
    for k in random.Random(5).sample(range(len(pool)), 4):
        msg, h = pool[k]
        assert h == B.hash_to_g2(msg) == hash_to_g2(msg)
        assert B.g2_in_subgroup(h)


def test_group_arithmetic_matches_the_oracle():
    from lighthouse_tpu.crypto.ref import curves as C

    rng = random.Random(9)
    for _ in range(3):
        k = rng.randrange(1, 2**40)
        assert B.g1_mul(B.G1, k) == C.g1_mul(C.G1_GEN, k)
        assert B.g2_mul(B.G2, k) == C.g2_mul(C.G2_GEN, k)
    secrets = sorted(rng.sample(range(1, 10**6), 300)) + [600, 1200]
    keys = B.pubkeys_of_secrets(secrets)
    for s in rng.sample(secrets, 10) + [600, 1200]:
        assert keys[s] == C.g1_mul(C.G1_GEN, s)
    ks = [rng.randrange(1, 2**30) for _ in range(20)]
    for k, p in zip(ks, B.g2_multiples(B.G2, ks)):
        assert p == C.g2_mul(C.G2_GEN, k)


def test_reference_verdict_agrees_with_the_pairing_check(pool):
    from lighthouse_tpu.crypto.ref import bls as RB

    class Meta:
        def __init__(self, secret, msg):
            self.secret, self.msg = secret, msg

    ref = Reference(pool)
    good = Meta(12345 + 678, 3)
    sig = B.g2_mul(pool[3][1], good.secret)
    pks = [B.g1_mul(B.G1, 12345), B.g1_mul(B.G1, 678)]
    assert ref.set_valid(good, sig)
    assert RB.verify_signature_sets([RB.SignatureSet(sig, pks, pool[3][0])])
    other = B.g2_mul(pool[4][1], good.secret)         # another message
    assert not ref.set_valid(good, other)
    assert not RB.verify_signature_sets(
        [RB.SignatureSet(other, pks, pool[3][0])])
