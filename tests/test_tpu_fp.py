"""Differential tests: JAX limb Fp arithmetic vs the pure-Python oracle."""

import random

import numpy as np
import jax.numpy as jnp
import pytest

from lighthouse_tpu.crypto.constants import P
from lighthouse_tpu.crypto.ref import fields as RF
from lighthouse_tpu.crypto.tpu import fp

rng = random.Random(0xB15)


def rand_fp(n):
    return [rng.randrange(P) for _ in range(n)]


def to_dev(xs):
    """ints -> Montgomery limb array (24, n)."""
    return fp.to_mont(jnp.asarray(fp.ints_to_array(xs)))


def from_dev(a):
    """Device (possibly lazily-reduced) Montgomery limbs -> canonical ints.
    The lazy representation returns any value ≡ x·R (mod p); host-side
    de-Montgomery + mod p recovers the canonical residue."""
    r_inv = pow(fp.R_INT, -1, P)
    return [(v * r_inv) % P for v in fp.array_to_ints(np.asarray(a))]


def test_limb_roundtrip():
    xs = rand_fp(7) + [0, 1, P - 1]
    arr = fp.ints_to_array(xs)
    assert fp.array_to_ints(arr) == xs


def test_mont_roundtrip():
    xs = rand_fp(5) + [0, 1, P - 1]
    a = to_dev(xs)
    back = [v % P for v in fp.array_to_ints(np.asarray(fp.from_mont(a)))]
    assert back == xs


def test_canonical_and_lazy_chains():
    """Deep lazy add/sub chains stay exact and `canonical` recovers the
    byte-exact residue (the lazy-reduction contract)."""
    if not hasattr(fp, "canonical"):
        import pytest

        pytest.skip("pre-lazy representation")
    n = 9
    xs, ys, zs = rand_fp(n), rand_fp(n), rand_fp(n)
    a, b, c = to_dev(xs), to_dev(ys), to_dev(zs)
    # (a - b + c + a - c)*b + (b - a) deep chain, no normalization
    acc = fp.add(fp.add(fp.sub(a, b), c), fp.sub(a, c))
    out = fp.add(fp.mont_mul(acc, b), fp.sub(b, a))
    want = [
        ((2 * x - y) * y + (y - x)) % P for x, y, z in zip(xs, ys, zs)
    ]
    assert from_dev(out) == want
    # canonical() produces byte-exact residues
    cano = np.asarray(fp.canonical(fp.from_mont(out)))
    got = fp.array_to_ints(cano)
    assert got == want
    assert cano.min() >= 0 and cano.max() < 256


@pytest.mark.parametrize("op,ref", [
    (fp.add, RF.fp_add),
    (fp.sub, RF.fp_sub),
    (fp.mont_mul, RF.fp_mul),
])
def test_binary_ops(op, ref):
    n = 17
    xs, ys = rand_fp(n), rand_fp(n)
    # exercise edge values too
    xs[:3] = [0, P - 1, 1]
    ys[:3] = [0, P - 1, P - 1]
    out = from_dev(op(to_dev(xs), to_dev(ys)))
    assert out == [ref(x, y) % P for x, y in zip(xs, ys)]


def test_neg():
    xs = rand_fp(5) + [0, 1, P - 1]
    out = from_dev(fp.neg(to_dev(xs)))
    assert out == [RF.fp_neg(x) for x in xs]


def test_inv():
    xs = rand_fp(4) + [1, P - 1, 0]
    out = from_dev(fp.inv(to_dev(xs)))
    expect = [RF.fp_inv(x) if x else 0 for x in xs]  # inv0 convention
    assert out == expect


def test_pow_fixed_exponent():
    xs = rand_fp(3)
    e = 0xD201000000010000
    out = from_dev(fp.mont_pow(to_dev(xs), e))
    assert out == [pow(x, e, P) for x in xs]


def test_eq_is_zero_select():
    xs = [5, 0, 7]
    ys = [5, 0, 8]
    a, b = to_dev(xs), to_dev(ys)
    assert list(np.asarray(fp.eq(a, b))) == [True, True, False]
    assert list(np.asarray(fp.is_zero(a))) == [False, True, False]
    sel = from_dev(fp.select(fp.eq(a, b), a, fp.neg(a)))
    assert sel == [5, 0, (-7) % P]


def test_broadcast_scalar_against_batch():
    xs = rand_fp(6)
    c = fp.const(3, ())
    out = from_dev(fp.mont_mul(c[:, None] if c.ndim == 1 else c, to_dev(xs)))
    assert out == [(3 * x) % P for x in xs]


def test_multi_dim_batch():
    xs = rand_fp(6)
    a = to_dev(xs).reshape(fp.NLIMB, 2, 3)
    out = fp.mont_mul(a, a).reshape(fp.NLIMB, 6)
    assert from_dev(out) == [(x * x) % P for x in xs]


# ------------------------------------------- mont_mul on the lazy domain

R_INV = pow(fp.R_INT, -1, P)
EDGES = [0, 1, P - 1, P, -1, -P, -5, 1000 * P, -1000 * P]


def lazy_limbs(values, bound, signed=True):
    """(NLIMB, n) int32 limbs holding each value exactly.  signed: digits
    in [-128, 127] made redundant by random value-preserving moves of
    up to bound/256 between neighbours, so limbs come near `bound`;
    unsigned: bytes in [0, 255] below a signed top limb (a negative value
    then has top limb < 0, which `_compress_limbs` wraps)."""
    cols = []
    for v in values:
        limbs = []
        for _ in range(fp.NLIMB - 1):
            d = (v + 128) % 256 - 128 if signed else v % 256
            limbs.append(d)
            v = (v - d) >> fp.LB
        limbs.append(v)
        r = bound // 256 - 1
        for i in range(fp.NLIMB - 1):
            k = rng.randint(-r, r) if r > 0 else 0
            limbs[i] += k << fp.LB
            limbs[i + 1] -= k
        cols.append(limbs)
    out = np.array(cols, dtype=np.int64).T
    assert np.abs(out).max() < 2 ** 22
    return out.astype(np.int32)


def lazy_values(n):
    return EDGES + [rng.randrange(-1000 * P, 1000 * P) for _ in range(n)]


def assert_mont_output(out, xs, ys):
    """`out` is mont_mul(x, y): ≡ x·y·R^-1 (mod p), limbs below the top
    in [-1, 257], the top limb in [-3, 260] (fp.mont_mul docstring)."""
    out = np.asarray(out)
    got = fp.array_to_ints(out)
    want = [RF.fp_mul(x % P, y % P) * R_INV % P for x, y in zip(xs, ys)]
    assert [g % P for g in got] == want
    assert out[:-1].min() >= -1 and out[:-1].max() <= 257
    assert out[-1].min() >= -3 and out[-1].max() <= 260


def exact_quotient(a, b):
    """The integer mont_mul(a, b) must equal: (a'·b' + m·p) / R, with a',
    b' the compressed operands and m the Montgomery quotient in the limb
    form mont_mul builds, all read back as Python integers."""
    ar, br = fp._compress_limbs(a), fp._compress_limbs(b)
    t_red = fp._compress_mod_R(fp._mul_cols_shift(ar, br)[:fp.NLIMB])
    m_red = fp._compress_mod_R(fp._mul_cols_shift(
        t_red, jnp.asarray(fp.NPRIME_LIMBS)[:, None], fp.NLIMB))
    ts = [x * y for x, y in zip(fp.array_to_ints(np.asarray(ar)),
                                fp.array_to_ints(np.asarray(br)))]
    us = [t + m * P for t, m in zip(ts, fp.array_to_ints(np.asarray(m_red)))]
    assert all(u % fp.R_INT == 0 for u in us)
    return [u // fp.R_INT for u in us]


@pytest.mark.parametrize("bound_a,signed_a,bound_b,signed_b", [
    (256, True, 256, True),                  # canonical-size limbs
    (2 ** 21, True, 2 ** 21, True),          # limbs near 2^21
    (256, False, 256, False),                # negative tops, wrapped ~R
    (2 ** 21, True, 256, False),
    (2 ** 21, False, 2 ** 21, True),
    (2 ** 22 - 2 ** 14, True, 2 ** 22 - 2 ** 14, False),   # near 2^22
])
def test_mont_mul_lazy_domain(bound_a, signed_a, bound_b, signed_b):
    """Signed redundant limbs near 2^21, values to ±1000p, and 0, 1, p-1,
    p: the product matches the oracle, is the exact quotient u/R (the
    same integer a carry-propagating division gives), and the limbs stay
    in bounds."""
    xs, ys = lazy_values(23), lazy_values(23)
    rng.shuffle(ys)
    a = jnp.asarray(lazy_limbs(xs, bound_a, signed_a))
    b = jnp.asarray(lazy_limbs(ys, bound_b, signed_b))
    assert fp.array_to_ints(a) == xs and fp.array_to_ints(b) == ys
    out = fp.mont_mul(a, b)
    assert_mont_output(out, xs, ys)
    assert fp.array_to_ints(np.asarray(out)) == exact_quotient(a, b)


def test_mont_mul_squaring_chain_stays_in_bounds():
    """Outputs fed back as inputs (the lazy domain is closed): 20
    squarings from the edge values keep the bounds and the value."""
    xs = EDGES + rand_fp(7)
    a = jnp.asarray(lazy_limbs(xs, 256, signed=False))
    vals = list(xs)
    for _ in range(20):
        a = fp.mont_mul(a, a)
        assert_mont_output(a, vals, vals)
        vals = [v * v * R_INV % P for v in vals]


def columns_multiple_of_R(n, low_spill):
    """(2N, n) int32 columns in (-2^23, 2^23) whose value is a multiple
    of R, built so `_exact_div_R`'s folded low half has its limbs below
    the top worth 0 (low_spill False) or 2^384 (True), or random (None)."""
    N = fp.NLIMB
    u = np.array([[rng.randrange(-2 ** 22, 2 ** 22) for _ in range(n)]
                  for _ in range(2 * N)], dtype=np.int64)
    for j in range(n):
        if low_spill is None:
            low = sum(int(u[k, j]) << (8 * k) for k in range(N))
            r = low % fp.R_INT                  # make the low half ≡ 0 mod R
            for k in range(N):
                u[k, j] -= (r >> (8 * k)) & 0xFF
        else:
            c = rng.randrange(-2 ** 14, 2 ** 14)
            if low_spill:                       # 256 + 255·(2^8+…+2^376)
                u[:N, j] = [256] + [255] * (N - 2) + [256 * c - 1]
            else:
                u[:N, j] = [0] * (N - 1) + [256 * c]
    return u.astype(np.int32)


def column_value(u):
    return [sum(int(u[k, j]) << (8 * k) for k in range(u.shape[0]))
            for j in range(u.shape[1])]


@pytest.mark.parametrize("low_spill", [False, True, None])
def test_exact_div_R_both_carry_branches(low_spill):
    """u/R exactly, whichever value (0 or 2^384) the folded low half's
    limbs below its top take; the branch is asserted, not assumed."""
    u = columns_multiple_of_R(16, low_spill)
    low = np.asarray(fp._compress_keep(jnp.asarray(u[:fp.NLIMB])))
    spill = low[-2] > 128
    if low_spill is None:
        assert spill.any()
    else:
        assert (spill == low_spill).all()
    out = np.asarray(fp._exact_div_R(jnp.asarray(u)))
    vals = column_value(u)
    assert all(v % fp.R_INT == 0 for v in vals)
    assert fp.array_to_ints(out) == [v // fp.R_INT for v in vals]
    assert out[:-1].min() >= -1 and out[:-1].max() <= 257


@pytest.mark.parametrize("low_spill", [False, True])
def test_mont_mul_both_carry_branches(monkeypatch, low_spill):
    """Both branches of the carry bit through mont_mul itself.  Folded
    low half worth 0 below its top: a = k·2^384 and b = j with
    k·j ≡ 0 (mod 256) make t = a·b a multiple of R, so m = 0 and u's
    low half is one top column (k in [0, 256) keeps a's top limb out of
    `_compress_limbs`' wrap).  Worth 2^384: canonical operands."""
    if low_spill:
        a, b = to_dev(rand_fp(8)), to_dev(rand_fp(8))
        xs = fp.array_to_ints(np.asarray(a))
        ys = fp.array_to_ints(np.asarray(b))
    else:
        ks = [16, 32, 0, 64, 128, 1, 100, 8]
        js = [16, -8, 5, 12, 2, 0, -64, 32]
        xs, ys = [k << 384 for k in ks], js
        a = jnp.asarray(lazy_limbs(xs, 256))
        b = jnp.asarray(lazy_limbs(ys, 256))
    seen = []
    div = fp._exact_div_R

    def spy(u):
        seen.append(np.asarray(fp._compress_keep(u[:fp.NLIMB]))[-2] > 128)
        return div(u)

    monkeypatch.setattr(fp, "_exact_div_R", spy)
    out = fp.mont_mul(a, b)
    assert len(seen) == 1 and (seen[0] == low_spill).all()
    assert_mont_output(out, xs, ys)
    if not low_spill:                           # exact: t/R = k·j/256
        assert fp.array_to_ints(np.asarray(out)) == [
            k * j // 256 for k, j in zip(ks, js)]


def test_mont_mul_has_no_sequential_loop():
    """The division by R is scan-free: no scan or while in mont_mul."""
    import jax

    x = jnp.zeros((fp.NLIMB, 4), jnp.int32)
    text = str(jax.make_jaxpr(fp.mont_mul)(x, x))
    assert "scan" not in text and "while" not in text


# ------------------------------------- constant-operand products (dots)

CONST_MATS = {"T_NP": (fp.T_NP, fp.NPRIME_LIMBS), "T_P": (fp.T_P, fp.P_LIMBS)}
ADMITTED = 1024                     # |limbs| `_mul_const_cols` admits


def const_operand(kind, batch):
    """(NLIMB, *batch) int32 limbs for `_mul_const_cols`: all at the low
    or the high end of its admitted range, random inside it, or random
    over `_compress_mod_R`'s output range [-1, 257] (what mont_mul
    passes) with both of its ends present."""
    shape = (fp.NLIMB,) + batch
    nrng = np.random.default_rng(len(batch) * 7 + len(kind))
    if kind == "low_end":
        return np.full(shape, -ADMITTED, np.int32)
    if kind == "high_end":
        return np.full(shape, ADMITTED, np.int32)
    if kind == "random":
        return nrng.integers(-ADMITTED, ADMITTED + 1, shape).astype(np.int32)
    x = nrng.integers(-1, 258, shape).astype(np.int32)
    flat = x.reshape(fp.NLIMB, -1)
    flat[0, 0], flat[-1, -1] = -1, 257
    return x


@pytest.mark.parametrize("kind", ["low_end", "high_end", "random",
                                  "mod_R_range"])
@pytest.mark.parametrize("batch", [(), (5,), (3, 4), (2, 3, 2)])
@pytest.mark.parametrize("mat", sorted(CONST_MATS))
def test_mul_const_cols_matches_shift_product(mat, batch, kind):
    """The Toeplitz dot equals the shift-form column sums bit for bit,
    at batch ranks 0-3 and both ends of the admitted limb range."""
    t, c = CONST_MATS[mat]
    x = jnp.asarray(const_operand(kind, batch))
    c_b = jnp.asarray(c)[(...,) + (None,) * len(batch)]
    got = np.asarray(fp._mul_const_cols(x, t))
    want = np.asarray(fp._mul_cols_shift(x, c_b, t.shape[0]))
    assert got.dtype == want.dtype == np.int32
    assert got.shape == want.shape == (t.shape[0],) + batch
    assert (got == want).all()


def test_toeplitz_constants_are_the_limb_products():
    """T_c · x is the integer product c·x read in limbs: its columns
    below R for T_NP (value ≡ c·x mod R), all of them for T_P."""
    x = fp.int_to_limbs(rng.randrange(fp.R_INT)).astype(np.int64)
    want = {m: fp.limbs_to_int(c) * fp.limbs_to_int(x)
            for m, (_, c) in CONST_MATS.items()}
    values = {m: sum(int(v) << (8 * k) for k, v in
                     enumerate(t.astype(np.int64) @ x))
              for m, (t, _) in CONST_MATS.items()}
    assert values["T_P"] == want["T_P"]
    assert values["T_NP"] % fp.R_INT == want["T_NP"] % fp.R_INT


LIMB_TOP = 2 ** 22 - 1


def extreme_limbs(kind, n):
    """(NLIMB, n) int32 mont_mul operands at the ends of its admitted
    input range (|limbs| < 2^22) and inside it."""
    shape = (fp.NLIMB, n)
    nrng = np.random.default_rng(n + len(kind))
    if kind == "max":
        return np.full(shape, LIMB_TOP, np.int32)
    if kind == "min":
        return np.full(shape, -LIMB_TOP, np.int32)
    if kind == "alternating":
        signs = np.where(np.arange(fp.NLIMB) % 2 == 0, 1, -1)[:, None]
        return np.broadcast_to(signs * LIMB_TOP, shape).astype(np.int32)
    if kind == "random":
        return nrng.integers(-LIMB_TOP, LIMB_TOP + 1, shape).astype(np.int32)
    return fp.ints_to_array(rand_fp(n))                       # canonical


LIMB_KINDS = ["max", "min", "alternating", "random", "canonical"]


@pytest.mark.parametrize("kind_a", LIMB_KINDS)
def test_mont_mul_matches_bigint_at_extremes(kind_a):
    """mont_mul of extreme operands equals the host's big-integer
    Montgomery product (mod p, and exactly the quotient u/R), every kind
    of a against every kind of b, within the output limb bounds."""
    n = 4
    a = np.concatenate([extreme_limbs(kind_a, n)] * len(LIMB_KINDS), axis=1)
    b = np.concatenate([extreme_limbs(k, n) for k in LIMB_KINDS], axis=1)
    a, b = jnp.asarray(a), jnp.asarray(b)
    out = fp.mont_mul(a, b)
    assert_mont_output(out, fp.array_to_ints(np.asarray(a)),
                       fp.array_to_ints(np.asarray(b)))
    assert fp.array_to_ints(np.asarray(out)) == exact_quotient(a, b)


# flat batches, and the batch shapes verify programs multiply at on the
# chip: (3, 3, 32) and (3, 2, 64) in the gossip program, (4, 32, 256),
# and the block program's lane-dense (32, 512)
KERNEL_BATCH_SHAPES = [(1,), (7,), (256,), (261,),
                       (3, 3, 32), (3, 2, 64), (4, 32, 256), (32, 512)]


@pytest.mark.parametrize("batch", KERNEL_BATCH_SHAPES,
                         ids=lambda b: "x".join(map(str, b)))
def test_mont_mul_matches_bigint_at_kernel_batch_shapes(batch):
    """mont_mul over a multi-axis batch equals the host's big-integer
    Montgomery product a·b·R^-1 mod p in every element, within the
    output limb bounds: canonical operands in even columns of the last
    axis, lazily reduced ones (signed limbs up to 2^21) in odd ones."""
    import jax

    n = int(np.prod(batch))
    nrng = np.random.default_rng(n)

    def operand():
        canon = fp.ints_to_array(rand_fp(n))
        lazy = nrng.integers(-2 ** 21, 2 ** 21, (fp.NLIMB, n), np.int32)
        odd = np.arange(n) % batch[-1] % 2 == 1
        return np.where(odd, lazy, canon).reshape((fp.NLIMB,) + batch)

    a, b = operand(), operand()
    out = jax.jit(fp.mont_mul)(jnp.asarray(a), jnp.asarray(b))
    assert out.shape == (fp.NLIMB,) + batch
    assert_mont_output(out.reshape(fp.NLIMB, n), fp.array_to_ints(a),
                       fp.array_to_ints(b))


def test_mont_mul_structure_two_constant_dots_one_shift_product():
    """mont_mul's constant products are two dots against the Toeplitz
    constants, at HIGHEST precision, and its a·b product is the one
    shift-form product (a single diagonal reduce_sum)."""
    import jax

    x = jnp.zeros((fp.NLIMB, 4), jnp.int32)
    closed = jax.make_jaxpr(fp.mont_mul)(x, x)
    consts = dict(zip(closed.jaxpr.constvars, closed.consts))
    prims = [e.primitive.name for e in closed.jaxpr.eqns]
    dots = [e for e in closed.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    lhs = [np.asarray(consts[d.invars[0]]) for d in dots]
    assert lhs[0].shape == fp.T_NP.shape and (lhs[0] == fp.T_NP).all()
    assert lhs[1].shape == fp.T_P.shape and (lhs[1] == fp.T_P).all()
    for d in dots:
        assert d.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
    assert prims.count("reduce_sum") == 1
