"""Base-field (Fp, p = BLS12-381 prime) limb arithmetic in JAX.

Representation (round-3 "lazy reduction" redesign): an Fp element is an
``int32`` array of shape ``(49, *batch)`` — 49 little-endian 8-bit SIGNED
limbs, value kept in **Montgomery form** (x·R mod p, R = 2^392) but only
LAZILY reduced: limb magnitudes stay small enough that every product is
exact in f32, yet no full carry propagation happens outside the zero
tests (`is_zero`, `canonical`).

Why this shape:
  * 8-bit limbs make the schoolbook product a set of f32-exact diagonal
    sums (`_mul_cols_shift`): products < 2^18 and 49-term column sums
    < 2^24 are exactly representable in f32 — the MXU/VPU-friendly core.
  * SIGNED limbs make subtraction a single elementwise op (a - b), with
    no borrow chain and no additive-constant tricks.
  * The 49th limb (R = 2^392 instead of 2^384) is headroom above p <
    2^381.  `mont_mul` first compresses each operand (value kept mod p,
    spills wrapped through 2^392 mod p and 2^400 mod p) to limbs in
    [-254, 510] with a top limb in [-1, 256], whatever its lazy history;
    so products stay f32-exact and every output is an exact N-limb
    integer with |limbs| <= 260 (`mont_mul`).  The lazy domain is closed
    by limb bounds: chains of ~30 lazy additions between multiplications
    stay far inside |limbs| < 2^22.
  * `add`/`sub`/`neg` are ONE elementwise HLO op each (round-2 cost:
    a 48-step `lax.scan` carry/borrow chain per call).  `mont_mul` costs
    one shift-formulation column product (a·b), two dots against
    constant Toeplitz matrices (t·N′ and m·p, `_mul_const_cols`) and a
    handful of fold passes, with NO sequential loop: in the exact
    division by R (`_exact_div_R`) the folded low half's limbs below its
    top are worth 0 or 2^384, and one compare on limb 47 says which.
    XLA compile time for the pairing graph is linear in per-field-op
    HLO cost (measured in round 3), so this representation is the
    second half of the compile-cliff fix; on TPU every scan step is one
    iteration of a device while loop, which is why the multiply has
    none.

Zero tests and equality are the only places full reduction happens, and
the only carry scans (`_carry_scan`): `is_zero` compresses through one
Montgomery step (zero is preserved), adds 4p, carry-propagates once, and
compares against the five canonical multiples of p its range admits.
`canonical` (for sgn0 / compressed-point sign rules) additionally
subtracts the right multiple of p picked by a scan-free lexicographic
compare.

This mirrors what blst does in spirit — redundant representations,
reduction only where semantics demand it (/root/reference/crypto/bls/
src/impls/blst.rs mul_mont_384's unreduced intermediate forms) — but
restructured for a vector machine instead of x86 scalar carries.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..constants import P

I32 = jnp.int32
F32 = jnp.float32
U32 = jnp.uint32                     # legacy alias (rand scalars etc.)
LB = 8                               # bits per limb
NLIMB = 49                           # 49 * 8 = 392 > 381 + 10 headroom bits
MASK = np.int32((1 << LB) - 1)
R_BITS = NLIMB * LB                  # Montgomery R = 2^392
R_INT = 1 << R_BITS
R1 = R_INT % P                       # R mod p  (= Montgomery form of 1)
R2 = (R_INT * R_INT) % P             # R^2 mod p (to_mont multiplier)
NPRIME = (-pow(P, -1, R_INT)) % R_INT   # -p^-1 mod R


def int_to_limbs(x: int) -> np.ndarray:
    """Host-side: python int in [0, R) -> (NLIMB,) int32 limb array."""
    assert 0 <= x < R_INT
    return np.frombuffer(x.to_bytes(NLIMB, "little"), dtype=np.uint8).astype(
        np.int32
    )


def limbs_to_int(a) -> int:
    """Host-side: limb array (NLIMB, no batch) -> python int (signed limbs
    handled exactly; result may be any integer congruent to the value)."""
    a = np.asarray(a)
    assert a.shape == (NLIMB,), a.shape
    # fast bytes path ONLY when every limb is verified in [0, 256) —
    # dtype alone proves nothing about magnitude
    if a.size and a.min() >= 0 and a.max() < 256:
        return int.from_bytes(a.astype(np.uint8).tobytes(), "little")
    return sum(int(v) << (LB * i) for i, v in enumerate(a))


def ints_to_array(xs) -> np.ndarray:
    """Host-side: list of ints -> (NLIMB, len) int32 array (batch trailing)."""
    xs = list(xs)
    if not xs:
        return np.zeros((NLIMB, 0), dtype=np.int32)
    buf = b"".join(int(x).to_bytes(NLIMB, "little") for x in xs)
    a = np.frombuffer(buf, dtype=np.uint8).reshape(len(xs), NLIMB)
    return np.ascontiguousarray(a.T).astype(np.int32)


def int_to_mont_limbs(x: int) -> np.ndarray:
    """Host-side Montgomery map: int -> (NLIMB,) canonical int32 limbs of
    x·R mod p.  One bigint mulmod, no device involvement — the staging
    path of the verify pipeline's host-prep stage."""
    return int_to_limbs((int(x) * R_INT) % P)


def ints_to_mont_array(xs) -> np.ndarray:
    """Host-side batch Montgomery map: ints -> (NLIMB, len) int32 limbs
    (batch trailing), each column x·R mod p."""
    return ints_to_array([(int(x) * R_INT) % P for x in xs])


def array_to_ints(a) -> list:
    a = np.asarray(a)
    flat = a.reshape(NLIMB, -1)
    if flat.size and flat.min() >= 0 and flat.max() < 256:
        cols = np.ascontiguousarray(flat.T).astype(np.uint8)
        return [
            int.from_bytes(cols[j].tobytes(), "little")
            for j in range(cols.shape[0])
        ]
    return [
        sum(int(flat[i, j]) << (LB * i) for i in range(NLIMB))
        for j in range(flat.shape[1])
    ]


P_LIMBS = int_to_limbs(P)
NPRIME_LIMBS = int_to_limbs(NPRIME)
R2_LIMBS = int_to_limbs(R2)
# wraparound constants for value-preserving folds: the fold passes shift
# high bytes one limb up, so the TOP limb's high byte would fall off the
# 49-limb representation; re-injecting it times (2^392 mod p) / (2^400
# mod p) keeps the VALUE congruent mod p while shrinking it.  Both
# constants have small top limbs (2^392 mod p ~ 0.06p, 2^400 mod p ~
# 0.55p < 2^381), so the feedback converges geometrically.
R392_LIMBS = int_to_limbs((1 << 392) % P)
R400_LIMBS = int_to_limbs((1 << 400) % P)
ONE_MONT = int_to_limbs(R1)           # 1 in Montgomery form
ONE_PLAIN = np.zeros(NLIMB, dtype=np.int32)
ONE_PLAIN[0] = 1                      # plain 1: mont_mul(a, this) == a/R
ZERO_LIMBS = np.zeros(NLIMB, dtype=np.int32)
# canonical limb arrays of k*p for the zero-test compare set and the
# canonicalization subtract set
_KP_LIMBS = np.stack([int_to_limbs(k * P) for k in range(0, 8)])


# ---------------------------------------------------------------- helpers

def _bshape(*arrs):
    """Broadcast batch shape of limb arrays (limbs axis 0 removed)."""
    return jnp.broadcast_shapes(*[a.shape[1:] for a in arrs])


def zeros(batch_shape=()):
    return jnp.zeros((NLIMB,) + tuple(batch_shape), I32)


def _carry_scan(cols, n_out):
    """Propagate carries over signed `cols` (M, *batch), |cols| < 2^30.

    Returns (n_out normalized limbs in [0, 255], final signed carry).
    One sequential `lax.scan`, paid by `is_zero` and `canonical` only:
    never per add/sub/mont_mul.
    """
    init = jnp.zeros(cols.shape[1:], I32)

    def step(carry, col):
        t = col + carry
        return t >> LB, t & MASK       # arithmetic shift: exact for signed

    carry, out = lax.scan(step, init, cols)
    if n_out > cols.shape[0]:
        pad = jnp.zeros((n_out - cols.shape[0],) + cols.shape[1:], I32)
        out = jnp.concatenate([out, pad], axis=0)
    return out[:n_out], carry


def _fold(cols, n_out):
    """One redundant carry fold (signed): high bytes shift up a limb.

    TRUNCATING at n_out: value preserved mod 2^(LB*n_out) only — use for
    the Montgomery-quotient pipeline (which is mod R by definition); use
    the _w variants where the value itself must be preserved mod p.
    """
    lo = cols & MASK
    hi = cols >> LB
    shifted = jnp.concatenate(
        [jnp.zeros((1,) + cols.shape[1:], I32), hi[: n_out - 1]], axis=0
    )
    return lo[:n_out] + shifted


def _fold3(cols, n_out):
    """Three-byte truncating fold for |columns| < 2^23 (signed-safe)."""
    b0 = cols & MASK
    b1 = (cols >> LB) & MASK
    b2 = cols >> (2 * LB)
    z1 = jnp.zeros((1,) + cols.shape[1:], I32)
    z2 = jnp.zeros((2,) + cols.shape[1:], I32)
    s1 = jnp.concatenate([z1, b1[: n_out - 1]], axis=0)
    s2 = jnp.concatenate([z2, b2[: n_out - 2]], axis=0)
    return b0[:n_out] + s1 + s2


def _bc(c_limbs, ndim):
    return jnp.asarray(c_limbs)[(...,) + (None,) * (ndim - 1)]


def _fold_w(cols):
    """Value-preserving fold to NLIMB limbs: the top limb's high byte is
    wrapped back in as spill * (2^392 mod p)."""
    lo = cols & MASK
    hi = cols >> LB
    out = lo + jnp.concatenate(
        [jnp.zeros((1,) + cols.shape[1:], I32), hi[:-1]], axis=0
    )
    return out + hi[-1][None] * _bc(R392_LIMBS, cols.ndim)


def _fold3_w(cols):
    """Value-preserving 3-byte fold to NLIMB limbs: spills at weights
    2^392 (from b1[-1], b2[-2]) and 2^400 (from b2[-1]) wrap through the
    matching (2^k mod p) constants."""
    b0 = cols & MASK
    b1 = (cols >> LB) & MASK
    b2 = cols >> (2 * LB)
    z1 = jnp.zeros((1,) + cols.shape[1:], I32)
    z2 = jnp.zeros((2,) + cols.shape[1:], I32)
    out = (
        b0
        + jnp.concatenate([z1, b1[:-1]], axis=0)
        + jnp.concatenate([z2, b2[:-2]], axis=0)
    )
    spill392 = b1[-1] + b2[-2]
    return (
        out
        + spill392[None] * _bc(R392_LIMBS, cols.ndim)
        + b2[-1][None] * _bc(R400_LIMBS, cols.ndim)
    )


def _compress_limbs(a):
    """Value-preserving compression of NLIMB signed limbs: |limbs| < 2^22
    in; out, limbs in [-254, 510] and the top limb in [-1, 256] (interval
    bounds over the three passes), so the value lies in (-2·2^384,
    258·2^384), congruent mod p (spills wrapped).  Three passes bound
    the wrap feedback: the wrap constants' top limbs are tiny, so each
    pass shrinks the spill by ~2^8."""
    assert a.shape[0] == NLIMB, a.shape
    return _fold_w(_fold_w(_fold3_w(a)))


def _compress_mod_R(a, n_out=NLIMB):
    """Truncating compression — ONLY for quantities defined mod R
    (the Montgomery quotient m)."""
    return _fold(_fold3(a, n_out), n_out)


def _fold_keep(cols):
    """Exact fold that keeps the top limb whole: every limb below the top
    splits into its low byte and a carry one limb up; the top limb takes
    its neighbour's carry and keeps its own high part (no wrap, no
    truncation: the value is unchanged)."""
    hi = cols >> LB
    lo = jnp.concatenate([cols[:-1] & MASK, cols[-1:]], axis=0)
    return lo + jnp.concatenate([jnp.zeros_like(cols[:1]), hi[:-1]], axis=0)


def _fold3_keep(cols):
    """Three-byte `_fold_keep`: limb k < N-2 spreads its bytes over limbs
    k..k+2; limb N-2 keeps its low byte and hands the rest, one limb up,
    to the top limb, which stays whole."""
    b1 = (cols >> LB) & MASK
    b2 = cols >> (2 * LB)
    z = jnp.zeros_like(cols[:2])
    out = jnp.concatenate([cols[:-1] & MASK, cols[-1:]], axis=0)
    s1 = jnp.concatenate([z[:1], b1[:-2], cols[-2:-1] >> LB], axis=0)
    s2 = jnp.concatenate([z, b2[:-2]], axis=0)
    return out + s1 + s2


def _compress_keep(cols):
    """Exact compression of |cols| < 2^24: every limb below the top lands
    in [-1, 257], the top limb holds the rest of the value.  The 3-byte
    fold leaves those limbs in [-256, 765] (b2 in [-256, 255]); the
    1-byte fold adds carries in [-1, 2] to low bytes in [0, 255]."""
    return _fold_keep(_fold3_keep(cols))


def _exact_div_R(u):
    """u / R, exactly, for columns u (2N, *batch), |u| < 2^24 - 2^16,
    whose value is a multiple of R: N limbs, the N-1 below the top in
    [-1, 257].

    No carry chain.  The low half compresses to a value c·R whose limbs
    below the top are in [-1, 257], so their value S lies in
    (-2^384/255, 1.008·2^384); S ≡ 0 mod 2^384 (the top limb's weight),
    so S is 0 or 2^384.  Limb N-2 tells which: S = 2^384 forces it to
    >= 255, S = 0 to <= 0, so it is > 128 iff S = 2^384.  Then
    c = (top + [S = 2^384]) / 256 exactly, and c joins the high half's
    limb 0 (|c| < 2^16, so |u| + |c| stays < 2^24)."""
    low = _compress_keep(u[:NLIMB])
    spill = (low[-2:-1] > (1 << (LB - 1))).astype(I32)
    carry = (low[-1:] + spill) >> LB
    high = jnp.concatenate([u[NLIMB:NLIMB + 1] + carry, u[NLIMB + 1:]], axis=0)
    return _compress_keep(high)


# public alias: ops whose outputs feed a mul-free linear recurrence (the
# cyclotomic 3T±2x path) must compress per iteration or limb magnitudes
# double every step and overflow int32 — everything routed through
# mont_mul is compressed as a side effect and needs nothing.
compress = _compress_limbs


# ------------------------------------------------------ column sums

def _mul_cols_shift(a, b, n_out=2 * NLIMB):
    """Schoolbook column sums via diagonal-sum reshape — no einsum, no
    big constants (~8 elementwise HLO ops; the compile-cliff fix of
    round 3).  cols[k] = sum_{i+j=k} a_i*b_j computed as diagonal
    sums of the flipped outer product through a (rows, L) -> (rows, L+1)
    flat reshape that shifts row i left by i.  Signed inputs are fine:
    f32 is exact for |products| < 2^24 and our |a_i|,|b_j| <= ~600.
    """
    bshape = _bshape(a, b)
    af = a.astype(F32)
    bf = b[::-1].astype(F32)                       # flip limb axis
    prods = af[:, None] * bf[None, :]              # (N, N, *batch)
    L = 3 * NLIMB - 2
    pad = [(0, 0), (NLIMB - 1, L - (2 * NLIMB - 1))] + [(0, 0)] * len(bshape)
    xp = jnp.pad(prods, pad)                       # (N, L, *batch)
    flat = xp.reshape((NLIMB * L,) + bshape)
    flat = jnp.concatenate(
        [flat, jnp.zeros((NLIMB,) + bshape, F32)], axis=0
    )
    v = flat.reshape((NLIMB, L + 1) + bshape)      # row i shifted left by i
    diags = v[:, : 2 * NLIMB - 1].sum(axis=0)      # (2N-1, *batch)
    cols = diags[::-1]
    if n_out > cols.shape[0]:
        cols = jnp.concatenate(
            [cols, jnp.zeros((n_out - cols.shape[0],) + bshape, F32)], axis=0
        )
    return cols[:n_out].astype(I32)


# ------------------------------------------- products by a constant
#
# For a host constant c the column sums are linear in x: cols = T_c · x
# with the Toeplitz matrix T_c[k, j] = c_{k-j}.  Over the limb axis that
# is one dot (49 values read a lane, n_out written), where the shift
# form materialises and relayouts a (49, 145) outer product.  The dot
# runs at Precision.HIGHEST, f32 arithmetic: on TPU the default rounds
# f32 operands to bf16, which holds integers exactly only up to 256.
# An explicit split x = 16·hi + lo against a bf16 [16·T_c | T_c] is
# exact too, and ran no faster on a v5e (PERF.md).

def _toeplitz(c_limbs, n_out):
    """(n_out, N) f32 matrix T_c[k, j] = c_{k-j} (zero outside 0..N-1)."""
    t = np.zeros((n_out, NLIMB), dtype=np.float32)
    for k in range(n_out):
        for j in range(max(0, k - NLIMB + 1), min(k, NLIMB - 1) + 1):
            t[k, j] = c_limbs[k - j]
    return t


T_NP = _toeplitz(NPRIME_LIMBS, NLIMB)      # t·N′ truncated mod R
T_P = _toeplitz(P_LIMBS, 2 * NLIMB)        # m·p, full width


def _mul_const_cols(x, t):
    """Column sums of x times the constant behind `t` (`T_NP`, `T_P`):
    bit-identical to `_mul_cols_shift(x, c)` truncated to t's rows.

    x: int32 (N, *batch), any batch rank, limbs in [-1024, 1024] — the
    output range of `_compress_mod_R`, [-1, 257], lies inside it.  The
    constants' limbs are in [0, 255], so a column's 49 products sum to
    at most 49·255·1024 < 2^24 in magnitude: every partial sum is an
    integer that f32 holds exactly, in any order of accumulation."""
    cols = lax.dot_general(
        t, x.astype(F32), (((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=F32,
    )
    return cols.astype(I32)


# ---------------------------------------------------------------- public ops

def add(a, b):
    """(a + b) — lazy: one elementwise op, no carry chain."""
    return a + b


def sub(a, b):
    """(a - b) — lazy: signed limbs make this one elementwise op."""
    return a - b


def neg(a):
    return -a


def mont_mul(a, b):
    """Montgomery product a·b·R^-1 mod p (SOS method, lazy domain).

    Accepts lazily-reduced inputs (|limbs| < 2^22, any value); returns
    the integer u/R exactly: limbs below the top in [-1, 257], the top
    limb in [-3, 260].  Cost: 2 compressions + the a·b column product +
    2 constant-operand dots (`_mul_const_cols`) + `_exact_div_R`'s four
    folds and one compare; no sequential loop.

    Correctness: the compressed operands a', b' have limbs in [-254, 510]
    and values in (-2·2^384, 258·2^384) (`_compress_limbs`), so every
    f32 product column is exact (49·510^2 < 2^24); m = t·(-p^-1) mod R
    is computed mod R by truncating folds, limbs in [-1, 257], so m lies
    in (-R/255, 1.008·R); u = t + m·p has columns in (-2^23, 2^24 - 2^16)
    and is ≡ 0 (mod R) as a VALUE even though its columns are nonzero,
    so `_exact_div_R(u)` is u/R.  u/R = (a'·b' + m·p)/R lies in
    (-2.02·2^384, 260.13·2^384) (p < 0.102·2^384), and the limbs below
    the top are worth (-2^384/255, 1.008·2^384), so the top limb, their
    difference over 2^384, is an integer in [-3, 260].
    """
    ar = _compress_limbs(a)
    br = _compress_limbs(b)
    cols_t = _mul_cols_shift(ar, br)                  # (2N, *batch) |.|<2^24
    t_red = _compress_mod_R(cols_t[:NLIMB])           # == t mod R
    m_red = _compress_mod_R(_mul_const_cols(t_red, T_NP))
    u = _mul_const_cols(m_red, T_P) + cols_t          # ≡ 0 mod R
    return _exact_div_R(u)


def mont_sqr(a):
    return mont_mul(a, a)


def to_mont(a):
    r2 = jnp.asarray(R2_LIMBS)[(...,) + (None,) * (a.ndim - 1)]
    return mont_mul(a, r2)


def from_mont(a):
    """Montgomery -> plain residue, lazily reduced (NOT canonical — use
    `canonical` where byte-exact representation matters)."""
    one = jnp.asarray(ONE_PLAIN)[(...,) + (None,) * (a.ndim - 1)]
    return mont_mul(a, one)


# Deliberately plain jit, NOT a compile_cache.CachedKernel: to_mont is
# called at whatever shapes host staging hands it (constants, curve
# points, ad-hoc tooling), so AOT-persisting one disk entry per shape
# would grow the cache without bound for a kernel that compiles in
# seconds.  The planner-canonicalized heavy kernels (bls, decompress)
# are where the AOT tier pays; jax's own compilation-cache tier covers
# this one's warm starts.
to_mont_jit = jax.jit(to_mont)


# ------------------------------------------------------- reduction points

def _eq_const(a, c_limbs):
    """Elementwise equality of canonical limbs against a host constant."""
    c = jnp.asarray(c_limbs)[(...,) + (None,) * (a.ndim - 1)]
    return jnp.all(a == c, axis=0)


def is_zero(a):
    """a ≡ 0 (mod p)?  Compress through one Montgomery step (zero is
    preserved: mont_mul(a, 1) = a/R mod p), shift positive, normalize
    once, and compare against the multiples of p the range admits."""
    w = from_mont(a)                                  # |value| < 2.3p
    four_p = jnp.asarray(_KP_LIMBS[4])[(...,) + (None,) * (a.ndim - 1)]
    v, carry = _carry_scan(w + four_p, NLIMB)         # value in (1.7p, 6.3p)
    hit = _eq_const(v, _KP_LIMBS[2])
    for k in (3, 4, 5, 6):
        hit = hit | _eq_const(v, _KP_LIMBS[k])
    return hit & (carry == 0)


def eq(a, b):
    return is_zero(a - b)


def _ge_const(a, c_limbs):
    """Scan-free lexicographic a >= c for canonical limb arrays: walk
    limbs most-significant-first with a cumulative all-equal prefix."""
    c = jnp.asarray(c_limbs)[(...,) + (None,) * (a.ndim - 1)]
    d = (a - c)[::-1]                                 # msb first
    eq_prefix = jnp.cumprod((d == 0).astype(I32), axis=0)
    higher_eq = jnp.concatenate(
        [jnp.ones((1,) + d.shape[1:], I32), eq_prefix[:-1]], axis=0
    )
    gt = jnp.any((d > 0) & (higher_eq == 1), axis=0)
    return gt | (eq_prefix[-1] == 1)


def canonical(a):
    """Fully-reduced canonical limbs in [0, p) — for sgn0 / compressed-
    point sign rules.  Operates on PLAIN-domain values (callers convert
    via `from_mont` first).  Two carry scans + one lex compare ladder."""
    four_p = jnp.asarray(_KP_LIMBS[4])[(...,) + (None,) * (a.ndim - 1)]
    v, _ = _carry_scan(a + four_p, NLIMB)             # canonical, < 8p
    # subtract the right multiple of p: k = #{kp <= v} over k=1..7
    k = jnp.zeros(v.shape[1:], I32)
    for kk in range(1, 8):
        k = k + _ge_const(v, _KP_LIMBS[kk]).astype(I32)
    table = jnp.asarray(_KP_LIMBS)                    # (8, NLIMB)
    kp = jnp.moveaxis(table[k], -1, 0)                # (NLIMB, *batch)
    out, _ = _carry_scan(v - kp, NLIMB)
    return out


def select(cond, a, b):
    """cond: batch-shaped bool; picks a where true."""
    return jnp.where(cond[None], a, b)


def _exp_bits(e: int) -> np.ndarray:
    n = max(e.bit_length(), 1)
    return np.array([(e >> i) & 1 for i in range(n)], dtype=np.bool_)


def mont_pow(a, e: int):
    """a^e (Montgomery in/out) by square-and-multiply scan over a
    compile-time bit array (LSB first)."""
    bits = jnp.asarray(_exp_bits(e))
    one = jnp.broadcast_to(
        jnp.asarray(ONE_MONT)[(...,) + (None,) * (a.ndim - 1)], a.shape
    )

    def step(state, bit):
        acc, base = state
        acc = jnp.where(bit, mont_mul(acc, base), acc)
        return (acc, mont_sqr(base)), None

    (acc, _), _ = lax.scan(step, (one, a), bits)
    return acc


def inv(a):
    """a^-1 via Fermat (a^(p-2)); maps 0 -> 0 mod p (RFC 9380 `inv0`)."""
    return mont_pow(a, P - 2)


def const(x: int, batch_shape=(), mont=True):
    v = (x * R_INT) % P if mont else x % P
    arr = jnp.asarray(int_to_limbs(v))
    return jnp.broadcast_to(
        arr[(...,) + (None,) * len(batch_shape)], (NLIMB,) + tuple(batch_shape)
    )


def to_int(a) -> int:
    """Host-side: Montgomery limb array (NLIMB,) -> canonical python int."""
    return (limbs_to_int(np.asarray(a)) * pow(R_INT, -1, P)) % P


def from_int(x: int, batch_shape=()):
    return const(x, batch_shape, mont=True)


# ----------------------------------------------- stacked-op helpers

def fstack(elems):
    """Stack Fp elements along a new axis 1: [(N,*B)] -> (N, n, *B)."""
    elems = jnp.broadcast_arrays(*elems)
    return jnp.stack(elems, axis=1)


def funstack(arr):
    return tuple(arr[:, i] for i in range(arr.shape[1]))


def tstack(trees):
    return jax.tree_util.tree_map(lambda *xs: fstack(xs), *trees)


def tunstack(tree, n):
    return [jax.tree_util.tree_map(lambda x: x[:, i], tree) for i in range(n)]
