"""Plain BLS12-381 arithmetic: the benchmark's own yardstick.

Integers mod P, Fp2 = Fp[u]/(u^2 + 1) as (c0, c1) tuples, the curves
E1: y^2 = x^3 + 4 over Fp and E2: y^2 = x^3 + 4(1 + u) over Fp2 in affine
(x, y) tuples (None is the point at infinity), Jacobian (X, Y, Z) inside
the loops, and RFC 9380 hash-to-G2 with the Ethereum proof-of-possession
ciphersuite.  Nothing here imports the system under test: the traffic
generator builds keys and signatures with it, and the reference verdict
(reference/verdicts.py) decides validity with it.

Speed matters only where set-up pays it: pubkeys of a sorted list of
secrets are walked with mixed additions and normalised in one batch
inversion, and many multiples of one point share a table of doublings.
"""

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
BLS_X = 0xD201000000010000          # the curve parameter is -BLS_X

G1 = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
G2 = (
    (0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
     0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
    (0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
     0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE),
)
B1 = 4
B2 = (4, 4)
DST_POP = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# ------------------------------------------------------------------- Fp2


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return ((-a[0]) % P, (-a[1]) % P)


def f2_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    return ((a0 * b0 - a1 * b1) % P, (a0 * b1 + a1 * b0) % P)


def f2_sqr(a):
    a0, a1 = a
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def f2_conj(a):
    return (a[0], (-a[1]) % P)


def f2_inv(a):
    n = pow((a[0] * a[0] + a[1] * a[1]) % P, P - 2, P)
    return (a[0] * n % P, (-a[1]) * n % P)


def f2_pow(a, e):
    out, base = (1, 0), a
    while e:
        if e & 1:
            out = f2_mul(out, base)
        base = f2_sqr(base)
        e >>= 1
    return out


def f2_is_zero(a):
    return a[0] % P == 0 and a[1] % P == 0


def _fp_sqrt(a):
    c = pow(a % P, (P + 1) // 4, P)
    return c if c * c % P == a % P else None


def f2_sqrt(a):
    """A square root in Fp2, or None for a non-residue."""
    a0, a1 = a[0] % P, a[1] % P
    if a1 == 0:
        s = _fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        t = _fp_sqrt(-a0)
        return None if t is None else (0, t)
    s = _fp_sqrt(a0 * a0 + a1 * a1)
    if s is None:
        return None
    inv2 = (P + 1) // 2
    for sign in (s, P - s):
        x0 = _fp_sqrt((a0 + sign) * inv2)
        if not x0:
            continue
        cand = (x0, a1 * pow(2 * x0, P - 2, P) % P)
        if f2_sqr(cand) == (a0, a1):
            return cand
    return None


def f2_sgn0(a):
    return (a[0] % 2) | ((a[0] % P == 0) & (a[1] % 2))


# ------------------------------------------------------ Jacobian curves


class _Fp:
    one = 1
    add = staticmethod(lambda a, b: (a + b) % P)
    sub = staticmethod(lambda a, b: (a - b) % P)
    mul = staticmethod(lambda a, b: a * b % P)
    sqr = staticmethod(lambda a: a * a % P)
    inv = staticmethod(lambda a: pow(a, P - 2, P))
    is_zero = staticmethod(lambda a: a % P == 0)


class _Fp2:
    one = (1, 0)
    add = staticmethod(f2_add)
    sub = staticmethod(f2_sub)
    mul = staticmethod(f2_mul)
    sqr = staticmethod(f2_sqr)
    inv = staticmethod(f2_inv)
    is_zero = staticmethod(f2_is_zero)


def _jdouble(F, p):
    X, Y, Z = p
    A = F.sqr(X)
    B = F.sqr(Y)
    C = F.sqr(B)
    t = F.sub(F.sub(F.sqr(F.add(X, B)), A), C)
    D = F.add(t, t)
    E = F.add(F.add(A, A), A)
    X3 = F.sub(F.sqr(E), F.add(D, D))
    C8 = F.add(C, C)
    C8 = F.add(C8, C8)
    C8 = F.add(C8, C8)
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), C8)
    YZ = F.mul(Y, Z)
    return (X3, Y3, F.add(YZ, YZ))


def _jmadd(F, p, q):
    """Jacobian p plus affine q (neither at infinity)."""
    X1, Y1, Z1 = p
    x2, y2 = q
    Z1Z1 = F.sqr(Z1)
    U2 = F.mul(x2, Z1Z1)
    S2 = F.mul(F.mul(y2, Z1), Z1Z1)
    H = F.sub(U2, X1)
    rr = F.sub(S2, Y1)
    if F.is_zero(H):
        if F.is_zero(rr):
            return _jdouble(F, p)
        return None
    HH = F.sqr(H)
    I = F.add(HH, HH)
    I = F.add(I, I)
    J = F.mul(H, I)
    r = F.add(rr, rr)
    V = F.mul(X1, I)
    X3 = F.sub(F.sub(F.sqr(r), J), F.add(V, V))
    YJ = F.mul(Y1, J)
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.add(YJ, YJ))
    Z3 = F.sub(F.sub(F.sqr(F.add(Z1, H)), Z1Z1), HH)
    return (X3, Y3, Z3)


def _to_affine(F, p):
    if p is None or F.is_zero(p[2]):
        return None
    zi = F.inv(p[2])
    zi2 = F.sqr(zi)
    return (F.mul(p[0], zi2), F.mul(p[1], F.mul(zi, zi2)))


def _batch_to_affine(F, points):
    """Affine forms of Jacobian points with one field inversion."""
    zs = [p[2] for p in points]
    prefix, acc = [], F.one
    for z in zs:
        prefix.append(acc)
        acc = F.mul(acc, z)
    inv = F.inv(acc)
    out = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        zi = F.mul(inv, prefix[i])
        inv = F.mul(inv, zs[i])
        zi2 = F.sqr(zi)
        X, Y, _ = points[i]
        out[i] = (F.mul(X, zi2), F.mul(Y, F.mul(zi, zi2)))
    return out


def _mul(F, pt, k):
    """[k]pt by double-and-add (affine in, affine out)."""
    if pt is None or k == 0:
        return None
    acc = None
    for bit in bin(k)[2:]:
        if acc is not None:
            acc = _jdouble(F, acc)
        if bit == "1":
            acc = (pt[0], pt[1], F.one) if acc is None else _jmadd(F, acc, pt)
            if acc is None:
                return None
    return _to_affine(F, acc)


def _add(F, p, q):
    if p is None:
        return q
    if q is None:
        return p
    return _to_affine(F, _jmadd(F, (p[0], p[1], F.one), q))


def g1_mul(pt, k):
    return _mul(_Fp, pt, k % R)


def g1_add(p, q):
    return _add(_Fp, p, q)


def g2_mul(pt, k):
    return _mul(_Fp2, pt, k % R)


def g2_add(p, q):
    return _add(_Fp2, p, q)


def g1_on_curve(pt):
    x, y = pt
    return (y * y - x * x * x - B1) % P == 0


def g2_on_curve(pt):
    x, y = pt
    return f2_sub(f2_sqr(y), f2_add(f2_mul(f2_sqr(x), x), B2)) == (0, 0)


def pubkeys_of_secrets(secrets):
    """{s: [s]G1} for positive secrets, walked in ascending order: each
    step adds [gap]G1, taken from a table of small multiples (gaps over
    the table are multiplied out), and all points are normalised with
    one inversion."""
    order = sorted(set(int(s) for s in secrets))
    if not order:
        return {}
    table = [None, G1]
    for _ in range(2, 257):
        table.append(_add(_Fp, table[-1], G1))
    first = _mul(_Fp, G1, order[0])
    X1, Y1, Z1 = first[0], first[1], 1
    xs, ys, zs = [X1], [Y1], [Z1]
    prev = order[0]
    p = P
    for s in order[1:]:
        gap = s - prev
        prev = s
        x2, y2 = table[gap] if gap < len(table) else _mul(_Fp, G1, gap)
        # mixed addition (madd-2007-bl, Z2 = 1)
        zz = Z1 * Z1 % p
        h = (x2 * zz - X1) % p
        if h == 0:
            # the step equals the running point (s is twice the last
            # secret): start again from [s]G1
            X1, Y1 = _mul(_Fp, G1, s)
            Z1 = 1
            xs.append(X1)
            ys.append(Y1)
            zs.append(Z1)
            continue
        rr = 2 * ((y2 * Z1 * zz - Y1) % p) % p
        hh = h * h % p
        i4 = 4 * hh % p
        j = h * i4 % p
        v = X1 * i4 % p
        X3 = (rr * rr - j - 2 * v) % p
        Y1 = (rr * (v - X3) - 2 * Y1 * j) % p
        Z1 = ((Z1 + h) * (Z1 + h) - zz - hh) % p
        X1 = X3
        xs.append(X1)
        ys.append(Y1)
        zs.append(Z1)
    # one inversion for every Z (Montgomery's trick)
    prefix, acc = [], 1
    for z in zs:
        prefix.append(acc)
        acc = acc * z % p
    inv = pow(acc, p - 2, p)
    out = {}
    for i in range(len(zs) - 1, -1, -1):
        zi = inv * prefix[i] % p
        inv = inv * zs[i] % p
        zi2 = zi * zi % p
        out[order[i]] = (xs[i] * zi2 % p, ys[i] * zi2 * zi % p)
    return out


def g2_multiples(pt, scalars):
    """[[k]pt for k in scalars] (all k > 0) through one table of
    doublings of pt and a single batch normalisation."""
    scalars = [int(k) % R for k in scalars]
    bits = max(scalars).bit_length()
    table = [(pt[0], pt[1], (1, 0))]
    for _ in range(bits - 1):
        table.append(_jdouble(_Fp2, table[-1]))
    table = _batch_to_affine(_Fp2, table)
    jac = []
    for k in scalars:
        acc = None
        j = 0
        while k:
            if k & 1:
                q = table[j]
                acc = (q[0], q[1], (1, 0)) if acc is None else _jmadd(
                    _Fp2, acc, q)
            k >>= 1
            j += 1
        jac.append(acc)
    return _batch_to_affine(_Fp2, jac)


# ----------------------------------------------------- RFC 9380 hash to G2

_SSWU_A = (0, 240)
_SSWU_B = (1012, 1012)
_SSWU_Z = (P - 2, P - 1)
_ISO_XNUM = (
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0,
     0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
     0),
)
_ISO_XDEN = (
    (0,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
    (0xC,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
    (1, 0),
)
_ISO_YNUM = (
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
     0),
)
_ISO_YDEN = (
    (0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
    (0,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3),
    (0x12,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99),
    (1, 0),
)
_XI = (1, 1)
_PSI_X = f2_inv(f2_pow(_XI, (P - 1) // 3))
_PSI_Y = f2_inv(f2_pow(_XI, (P - 1) // 2))


def expand_message_xmd(msg, dst, n):
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00"
                        + dst_prime).digest()
    out = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, -(-n // 32) + 1):
        mixed = bytes(x ^ y for x, y in zip(b0, out[-1]))
        out.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(out)[:n]


def hash_to_field(msg, dst=DST_POP):
    """The two Fp2 elements u0, u1 of RFC 9380 hash_to_field."""
    data = expand_message_xmd(msg, dst, 256)
    ints = [int.from_bytes(data[64 * i:64 * i + 64], "big") % P
            for i in range(4)]
    return (ints[0], ints[1]), (ints[2], ints[3])


def _sswu(u):
    A, B, Z = _SSWU_A, _SSWU_B, _SSWU_Z
    zu2 = f2_mul(Z, f2_sqr(u))
    tv = f2_add(f2_sqr(zu2), zu2)
    if f2_is_zero(tv):
        x1 = f2_mul(B, f2_inv(f2_mul(Z, A)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(B), f2_inv(A)), f2_add((1, 0), f2_inv(tv)))

    def g(x):
        return f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(A, x)), B)

    y = f2_sqrt(g(x1))
    x = x1
    if y is None:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(g(x))
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return x, y


def _poly(coeffs, x):
    acc = (0, 0)
    for c in reversed(coeffs):
        acc = f2_add(f2_mul(acc, x), c)
    return acc


def _iso3(pt):
    x, y = pt
    X = f2_mul(_poly(_ISO_XNUM, x), f2_inv(_poly(_ISO_XDEN, x)))
    Y = f2_mul(y, f2_mul(_poly(_ISO_YNUM, x), f2_inv(_poly(_ISO_YDEN, x))))
    return X, Y


def _psi(pt):
    return (f2_mul(_PSI_X, f2_conj(pt[0])), f2_mul(_PSI_Y, f2_conj(pt[1])))


def _neg2(pt):
    return None if pt is None else (pt[0], f2_neg(pt[1]))


def clear_cofactor(pt):
    """[h_eff]pt by RFC 9380 G.3, with the curve parameter x = -BLS_X."""
    t1 = _neg2(_mul(_Fp2, pt, BLS_X))               # [x]P
    t2 = _psi(pt)
    out = g2_add(_neg2(_mul(_Fp2, t1, BLS_X)), _neg2(t1))     # [x^2 - x]P
    out = g2_add(out, _neg2(pt))
    out = g2_add(out, _neg2(_mul(_Fp2, t2, BLS_X)))            # + [x]psi(P)
    out = g2_add(out, _neg2(t2))
    return g2_add(out, _psi(_psi(g2_add(pt, pt))))


def hash_to_g2(msg, dst=DST_POP):
    u0, u1 = hash_to_field(msg, dst)
    return clear_cofactor(g2_add(_iso3(_sswu(u0)), _iso3(_sswu(u1))))


def g2_in_subgroup(pt):
    """psi(P) == [x]P for P on E2 (x negative)."""
    return g2_on_curve(pt) and _psi(pt) == _neg2(_mul(_Fp2, pt, BLS_X))
