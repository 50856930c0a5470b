"""Device-side batched G2 signature decompression.

The host pipeline paid a pure-Python Fp2 square root (~ms) PER gossip
signature before any device work could start — at a 2048-attestation
batch that serial pre-pass dwarfs the verification itself.  Here the
whole batch decompresses in ONE device program: byte parsing and flag
checks stay host-side (numpy, cheap), the square root runs as batched
fixed-exponent Montgomery powers (lax.scan over a constant exponent —
the same schedule every other kernel uses), and every branch of the
norm-trick Fp2 sqrt (RFC 9380 / ref fields.f2_sqrt) becomes a lane
select.  Invalid encodings yield a False lane in the validity mask
instead of an exception — callers treat those sets as failed, exactly
like blst's CKERR paths (/root/reference/crypto/bls/src/impls/blst.rs).

Integration point: gossip batch prep (sync round-trips through
`signature_sets` still decompress host-side; wiring this in is the
round-3 fast path — the kernel itself is complete and differentially
tested against the oracle).

Backend economics (measured): on the CPU backend the five fixed-exponent
pow scans LOSE to host Python (3.1 ms/sig host vs ~119 ms/sig device at
batch 256 on one core) — this kernel is a TPU capability, and whether
a deployment uses it is a choice to make from a chip measurement.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..constants import P
from . import compile_cache as cc
from . import curve as cv
from . import fp
from . import sharding as _shard
from . import tower as tw


def _g2_subgroup_kernel(p):
    return cv.g2_in_subgroup(p)


_jit_g2_subgroup = cc.CachedKernel("g2_subgroup_check", _g2_subgroup_kernel)

# y^2 = x^3 + B2 with B2 = (4, 4)
_B2 = (4, 4)
_SQRT_EXP = (P + 1) // 4          # Fp sqrt candidate (P = 3 mod 4)
_HALF_P = (P - 1) // 2            # lexicographic "greater than half"
_INV2 = pow(2, -1, P)


def parse_g2_bytes(blobs):
    """Host: list of 96-byte compressed encodings -> (c0, c1 int lists,
    y_big flags, structural validity, infinity flags).  Pure byte work —
    no field math."""
    n = len(blobs)
    c0s, c1s = [0] * n, [0] * n
    y_big = np.zeros(n, dtype=bool)
    valid = np.zeros(n, dtype=bool)
    is_inf = np.zeros(n, dtype=bool)
    for i, raw in enumerate(blobs):
        b = bytes(raw)
        if len(b) != 96:
            continue
        flags = b[0]
        if not flags & 0x80:
            continue
        inf = bool(flags & 0x40)
        big = bool(flags & 0x20)
        body = bytes([flags & 0x1F]) + b[1:]
        if inf:
            if any(body) or big:
                continue
            valid[i] = True
            is_inf[i] = True
            continue
        c1 = int.from_bytes(body[:48], "big")
        c0 = int.from_bytes(body[48:], "big")
        if c0 >= P or c1 >= P:
            continue
        c0s[i], c1s[i] = c0, c1
        y_big[i] = big
        valid[i] = True
    return c0s, c1s, y_big, valid, is_inf


def _gt_half(a):
    """Canonical (non-Montgomery) limb array > (P-1)/2, per lane.
    a > (P-1)/2  <=>  a >= (P-1)/2 + 1 (both sides canonical < p)."""
    return fp._ge_const(a, fp.int_to_limbs(_HALF_P + 1))


def _sqrt_fp(a):
    """Candidate sqrt + validity per lane (a in Montgomery form)."""
    c = fp.mont_pow(a, _SQRT_EXP)
    ok = fp.eq(fp.mont_mul(c, c), a)
    return c, ok


def _sqrt_with_invroot(h):
    """(sqrt(h), h^((p-3)/4), valid): for square h the second value is
    1/sqrt(h) — saving the Fermat inversion downstream."""
    c = fp.mont_pow(h, (P - 3) // 4)
    x0 = fp.mont_mul(c, h)
    ok = fp.eq(fp.mont_mul(x0, x0), h)
    return x0, c, ok


def decompress_kernel(c0, c1, y_big):
    """Batched device decompression over Montgomery limb arrays.

    Returns Jacobian ((X, Y, Z) Fp2 pairs) + on-curve validity mask.
    Branchless: both halves of every oracle branch are computed, lanes
    select (f2_sqrt's a1==0 special case included)."""
    x = (c0, c1)
    y2 = tw.f2_add(tw.f2_mul(tw.f2_sqr(x), x), tw.f2_const(*_B2, c0.shape[1:]))
    a0, a1 = y2
    a1_zero = fp.is_zero(a1)

    # general case: norm trick
    n = fp.add(fp.mont_mul(a0, a0), fp.mont_mul(a1, a1))
    s, _ = _sqrt_fp(n)   # validity decided ONLY by the final square check
    inv2 = fp.const(_INV2, c0.shape[1:])
    h_plus = fp.mont_mul(fp.add(a0, s), inv2)
    h_minus = fp.mont_mul(fp.sub(a0, s), inv2)
    x0p, cp, okp = _sqrt_with_invroot(h_plus)
    x0m, cm, okm = _sqrt_with_invroot(h_minus)
    x0 = fp.select(okp, x0p, x0m)
    c = fp.select(okp, cp, cm)
    # x1 = a1 / (2 x0) without a Fermat inversion: for square h,
    # c = h^((p-3)/4) satisfies c * x0 = 1, so 1/(2 x0) = c / 2
    x1 = fp.mont_mul(fp.mont_mul(a1, c), inv2)
    cand_gen = (x0, x1)

    # a1 == 0: y = (sqrt(a0), 0) or (0, sqrt(-a0))
    r_re, re_ok = _sqrt_fp(a0)
    r_im, im_ok = _sqrt_fp(fp.neg(a0))
    cand_a1z = (
        fp.select(re_ok, r_re, fp.const(0, c0.shape[1:])),
        fp.select(re_ok, fp.const(0, c0.shape[1:]), r_im),
    )

    y = tw.f2_select(a1_zero, cand_a1z, cand_gen)
    # single validity rule: the selected candidate must square to y2
    valid = tw.f2_eq(tw.f2_sqr(y), y2)

    # sign normalization (ZCash lex rule: compare c1 unless zero, else
    # c0): flip so the encoded bit matches.  The lex compare needs the
    # CANONICAL residues (from_mont alone is lazily reduced).
    yc = fp.canonical(fp.from_mont(fp.fstack([y[0], y[1]])))
    y0c, y1c = fp.funstack(yc)
    # y1c is fully reduced into [0, p): the zero test is a free compare
    big = jnp.where(jnp.all(y1c == 0, axis=0), _gt_half(y0c), _gt_half(y1c))
    flip = big != y_big
    y = tw.f2_select(flip, tw.f2_neg(y), y)

    one = fp.const(1, c0.shape[1:], mont=True)
    zero = fp.const(0, c0.shape[1:])
    return (x, y, (one, zero)), valid


_jit_decompress = cc.CachedKernel("g2_decompress", decompress_kernel)


def g2_decompress_batch(blobs, subgroup_check=True):
    """Full batched decompression: 96-byte blobs -> device Jacobian
    points + validity mask (numpy bool).  Infinity encodings come back
    valid with Z = 0.

    `subgroup_check=True` (the oracle's and blst's default) also runs
    the device psi-based G2 subgroup check — an on-curve point outside
    the r-order subgroup gets ok=False.  Batches are padded onto the
    ShapePlanner's lane menu (compile_cache.py) so varying gossip sizes
    share a bounded, enumerable set of compiled shapes."""
    n = len(blobs)
    if n == 0:
        return None, np.zeros(0, dtype=bool)
    n_pad = cc.get_planner().plan_lanes(n)
    blobs = list(blobs) + [b""] * (n_pad - n)
    c0s, c1s, y_big, valid, is_inf = parse_g2_bytes(blobs)
    shape = (n_pad,)
    c0 = fp.to_mont_jit(jnp.asarray(fp.ints_to_array(c0s).reshape((fp.NLIMB,) + shape)))
    c1 = fp.to_mont_jit(jnp.asarray(fp.ints_to_array(c1s).reshape((fp.NLIMB,) + shape)))
    # the decompress pass shards its lane axis on dp like every other
    # device program (plan_lanes is already dp-rounded by the planner)
    plan = _shard.get_mesh_plan()
    (c0, c1), _ = plan.place_batched((c0, c1), axis=1)
    yb, _ = plan.place_batched(jnp.asarray(y_big), axis=0)
    (x, y, z), on_curve = _jit_decompress(c0, c1, yb)
    # profile-registry pad join: n real blobs rode n_pad planned lanes
    try:
        from . import profile

        label = cc.CompileCache._label_from_sig(
            cc._shape_sig((c0, c1, yb))[0]
        )
        profile.get_registry().record_pad("g2_decompress", label, n, n_pad)
    except Exception:
        pass
    ok = valid & (np.asarray(on_curve) | is_inf)
    # infinity lanes: zero Z (the kernel's Z is 1 everywhere)
    if is_inf.any():
        zmask = jnp.asarray(~is_inf)[None, :].astype(fp.I32)
        z = (z[0] * zmask, z[1])
    if subgroup_check:
        in_sub = np.asarray(_jit_g2_subgroup((x, y, z)))
        ok &= in_sub | is_inf
    return (
        jax.tree_util.tree_map(lambda a: a[..., :n], (x, y, z)),
        ok[:n],
    )
