"""Device-batched G2 decompression vs the oracle: valid signatures,
both sign bits, infinity, and every rejection class."""

import numpy as np
import pytest

# slow-marked while the compile-cliff work lands: the
# decompress kernel's cold compile dominates the whole fast lane on a
# machine whose XLA cache is empty.  Un-mark once cold compile is
# back under ~1 minute.
pytestmark = pytest.mark.slow

from lighthouse_tpu.crypto.ref import bls as RB
from lighthouse_tpu.crypto.ref import curves as C
from lighthouse_tpu.crypto.tpu import curve as cv
from lighthouse_tpu.crypto.tpu import decompress as dc


def _sigs(n, seed=5):
    import random

    rng = random.Random(seed)
    out = []
    for i in range(n):
        sk = rng.randrange(1, 2**200)
        out.append(C.g2_compress(RB.sign(sk, bytes([i]) * 32)))
    return out


def test_batch_matches_oracle_points():
    blobs = _sigs(16)
    (x, y, z), ok = dc.g2_decompress_batch(blobs, subgroup_check=False)
    assert ok.all()
    got = cv.g2_to_ints((x, y, z))
    for blob, pt in zip(blobs, got):
        assert pt == C.g2_decompress(blob, subgroup_check=False)


def test_sign_bit_both_ways():
    """A signature and its negation decompress to y and -y."""
    blob = _sigs(1)[0]
    pt = C.g2_decompress(blob, subgroup_check=False)
    neg = C.g2_compress((pt[0], C.F.f2_neg(pt[1])))
    (x, y, z), ok = dc.g2_decompress_batch([blob, neg], subgroup_check=False)
    assert ok.all()
    a, b = cv.g2_to_ints((x, y, z))
    assert a[0] == b[0]
    assert b[1] == C.F.f2_neg(a[1])


def test_infinity_and_rejections():
    inf = bytes([0xC0]) + bytes(95)
    wrong_len = b"\x00" * 95
    no_flag = bytes(96)                          # compressed bit missing
    bad_inf = bytes([0xE0]) + bytes(95)          # infinity + sign bit
    # x not on curve: x = (2, 0) has no y (2^3+B2 non-square w.h.p.)
    from lighthouse_tpu.crypto.ref.curves import _fp_to_bytes

    probe = None
    for xc in range(2, 40):
        y2 = C.F.f2_add(C.F.f2_mul(C.F.f2_sqr((xc, 0)), (xc, 0)), C.B2)
        if C.F.f2_sqrt(y2) is None:
            body = _fp_to_bytes(0) + _fp_to_bytes(xc)
            probe = bytes([body[0] | 0x80]) + body[1:]
            break
    assert probe is not None
    good = _sigs(1)[0]

    blobs = [inf, wrong_len, no_flag, bad_inf, probe, good]
    (x, y, z), ok = dc.g2_decompress_batch(blobs, subgroup_check=False)
    assert list(ok) == [True, False, False, False, False, True]
    pts = cv.g2_to_ints((x, y, z))
    assert pts[0] is None, "infinity lane has Z = 0"


def test_out_of_range_coordinate_rejected():
    from lighthouse_tpu.crypto.constants import P

    blob = bytearray(_sigs(1)[0])
    # overwrite c1 with P (canonical-form violation)
    c1 = (P).to_bytes(48, "big")
    blob[0:48] = c1
    blob[0] |= 0x80
    _, ok = dc.g2_decompress_batch([bytes(blob)])
    assert not ok.any()


def test_non_subgroup_point_rejected():
    """An on-curve point OUTSIDE the r-order subgroup passes the curve
    check but fails the default (blst-parity) validity mask."""
    from lighthouse_tpu.crypto.ref.curves import _fp_to_bytes

    rogue = None
    for xc in range(2, 60):
        x = (xc, 0)
        y2 = C.F.f2_add(C.F.f2_mul(C.F.f2_sqr(x), x), C.B2)
        y = C.F.f2_sqrt(y2)
        if y is not None and not C.g2_in_subgroup((x, y)):
            rogue = C.g2_compress((x, y))
            break
    assert rogue is not None
    good = _sigs(1)[0]
    _, ok_loose = dc.g2_decompress_batch([rogue, good], subgroup_check=False)
    assert list(ok_loose) == [True, True], "on-curve passes the loose mask"
    _, ok_strict = dc.g2_decompress_batch([rogue, good])
    assert list(ok_strict) == [False, True], "subgroup check rejects it"
