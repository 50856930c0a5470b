"""Base-field (Fp, p = BLS12-381 prime) limb arithmetic in JAX.

Representation (round-3 "lazy reduction" redesign): an Fp element is an
``int32`` array of shape ``(49, *batch)`` — 49 little-endian 8-bit SIGNED
limbs, value kept in **Montgomery form** (x·R mod p, R = 2^392) but only
LAZILY reduced: |value| stays within a few multiples of p and limb
magnitudes stay small enough that every product is exact in f32, yet no
carry propagation happens outside `mont_mul`.

Why this shape:
  * 8-bit limbs make the schoolbook product a set of f32-exact diagonal
    sums (`_mul_cols_shift`): products < 2^18 and 49-term column sums
    < 2^24 are exactly representable in f32 — the MXU/VPU-friendly core.
  * SIGNED limbs make subtraction a single elementwise op (a - b), with
    no borrow chain and no additive-constant tricks.
  * The 49th limb (R = 2^392 instead of 2^384) buys 2^10.35 of headroom
    over p ~ 2^381.65, which is what lets values wander in (-Bp, +Bp)
    between reductions: the Montgomery step maps inputs of magnitude
    B·p to outputs of magnitude ~(B^2·2^-10.35 + 1.008)·p, a contraction
    with fixed point B ~ 2.02 — chains of ~30 lazy additions between
    multiplications stay far inside the representable range.
  * `add`/`sub`/`neg` are ONE elementwise HLO op each (round-2 cost:
    a 48-step `lax.scan` carry/borrow chain per call).  `mont_mul` costs
    three shift-formulation column products, two fold passes and ONE
    carry scan.  XLA compile time for the pairing graph is linear in
    per-field-op HLO cost (measured in round 3), so this representation is
    the second half of the compile-cliff fix — and removes ~10^2 sequential
    48-step loops per curve op at RUNTIME, which is what the TPU VPU
    actually cares about.

Zero tests and equality are the only places full reduction happens:
`is_zero` compresses through one Montgomery step (zero is preserved),
adds 4p, carry-propagates once, and compares against the five canonical
multiples of p its range admits.  `canonical` (for sgn0 / compressed-
point sign rules) additionally subtracts the right multiple of p picked
by a scan-free lexicographic compare.

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
    One sequential `lax.scan`: this is the ONLY scan in the field layer,
    paid once per `mont_mul`/`is_zero`, never per add/sub.
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
    in, |limbs| <= ~260 out, value congruent mod p (spills wrapped).
    Three passes bound the wrap feedback: the wrap constants' top limbs
    are tiny, so each pass shrinks the spill by ~2^8."""
    assert a.shape[0] == NLIMB, a.shape
    return _fold_w(_fold_w(_fold3_w(a)))


def _compress_mod_R(a, n_out=NLIMB):
    """Truncating compression — ONLY for quantities defined mod R
    (the Montgomery quotient m)."""
    return _fold(_fold3(a, n_out), n_out)


# public alias: ops whose outputs feed a mul-free linear recurrence (the
# cyclotomic 3T±2x path) must compress per iteration or limb magnitudes
# double every step and overflow int32 — everything routed through
# mont_mul is compressed as a side effect and needs nothing.
compress = _compress_limbs


# ------------------------------------------------- column-sum candidates

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


# Constant antidiagonal-gather matrix for the einsum candidates (kept for
# the bench kernel shoot-out; the shift path is the default).
def _diag_mat():
    m = np.zeros((2 * NLIMB, NLIMB * NLIMB), dtype=np.float32)
    for i in range(NLIMB):
        for j in range(NLIMB):
            m[i + j, i * NLIMB + j] = 1.0
    return m


_DIAG_MAT = None


def _mul_cols_f32(a, b, n_out=2 * NLIMB):
    """einsum candidate: one f32 GEMM against a constant 0/1 gather
    matrix (HIGHEST precision is load-bearing on TPU — default bf16
    passes would corrupt the 16-bit limb products)."""
    global _DIAG_MAT
    if _DIAG_MAT is None:
        _DIAG_MAT = _diag_mat()
    bshape = _bshape(a, b)
    af = a.astype(F32)
    bf = b.astype(F32)
    prods = (af[:, None] * bf[None, :]).reshape((NLIMB * NLIMB,) + bshape)
    cols = jnp.einsum(
        "ks,s...->k...",
        jnp.asarray(_DIAG_MAT[:n_out]),
        prods,
        preferred_element_type=F32,
        precision=lax.Precision.HIGHEST,
    )
    return cols.astype(I32)


_DIAG_MAT_I32 = None


def _mul_cols_int32(a, b, n_out=2 * NLIMB):
    """int32-dot candidate (whether XLA puts it on the MXU is a per-
    backend measurement; bench.py answers it)."""
    global _DIAG_MAT_I32
    if _DIAG_MAT_I32 is None:
        _DIAG_MAT_I32 = _diag_mat().astype(np.int32)
    bshape = _bshape(a, b)
    ai = a.astype(jnp.int32)
    bi = b.astype(jnp.int32)
    prods = (ai[:, None] * bi[None, :]).reshape((NLIMB * NLIMB,) + bshape)
    cols = jnp.einsum(
        "ks,s...->k...",
        jnp.asarray(_DIAG_MAT_I32[:n_out]),
        prods,
        preferred_element_type=jnp.int32,
    )
    return cols.astype(I32)


import os as _os

_mul_cols = {
    "int32": _mul_cols_int32,
    "einsum": _mul_cols_f32,
    "f32": _mul_cols_f32,
}.get(_os.environ.get("LTPU_MULCOLS", "shift"), _mul_cols_shift)


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

    Accepts lazily-reduced inputs (|limbs| < 2^22, |value| < ~1000p);
    returns |value| < ~2.3p with limbs in [0,255] plus a {-1,0} top limb.
    Cost: 2 compressions + 3 column products + ONE carry scan.

    Correctness: with folded limbs <= 258, every f32 product column is
    exact (< 2^24); m = t·(-p^-1) mod R is computed mod R by truncating
    folds at NLIMB; u = t + m·p is ≡ 0 (mod R) as a VALUE even though its
    columns are nonzero, so after one full carry propagation the low
    NLIMB limbs are exactly zero and the high limbs (plus the final
    signed carry at weight 2^384... i.e. limb NLIMB-1 of the shifted
    result) are u/R.  |u/R| <= |a||b|/R + p < (B^2·2^-10.35 + 1.008)p —
    the contraction that makes the lazy domain closed (module docstring).
    """
    ar = _compress_limbs(a)
    br = _compress_limbs(b)
    cols_t = _mul_cols(ar, br)                        # (2N, *batch) |.|<2^23
    t_red = _compress_mod_R(cols_t[:NLIMB])           # == t mod R
    np_arr = jnp.asarray(NPRIME_LIMBS)[(...,) + (None,) * (cols_t.ndim - 1)]
    m_red = _compress_mod_R(_mul_cols(t_red, np_arr, NLIMB))
    p_arr = jnp.asarray(P_LIMBS)[(...,) + (None,) * (cols_t.ndim - 1)]
    u = _mul_cols(m_red, p_arr) + cols_t              # ≡ 0 mod R, |.|<2^23
    full, carry = _carry_scan(u, 2 * NLIMB)           # low NLIMB limbs = 0
    res = full[NLIMB:]                                # (NLIMB-1...) see below
    # full has 2N limbs; res = limbs N..2N-1 (N of them).  The scan's
    # final carry has weight 2^(8*2N) -> /R = weight 2^(8*(2N - N)) =
    # limb N of res — one PAST the top: fold it into the top limb with
    # weight 256 (exact: carry ∈ {-1, 0}).
    top = res[-1] + carry * (1 << LB)
    return jnp.concatenate([res[:-1], top[None]], axis=0)


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
