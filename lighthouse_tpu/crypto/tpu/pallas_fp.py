"""Experimental Pallas kernel: fused Montgomery multiplication.

The default `fp.mont_mul` is a chain of XLA ops (input compressions, three
`_mul_cols` contractions, redundant folds, the exact division by R); XLA
fuses much of it, but every stage still round-trips intermediates at the
fusion boundaries.  This kernel runs the WHOLE lazy-domain SOS Montgomery
multiply — both limb-product contractions, the Montgomery-quotient
contraction, the value-preserving input compressions, and the exact
division by R — as ONE `pallas_call` per batch tile: operands land in
VMEM once, the three contractions hit the MXU back-to-back, and only the
reduced result returns to HBM (pallas_guide.md: HBM->VMEM->compute).

It is a bit-for-bit mirror of `fp.mont_mul` on the lazy representation
(49 signed int32 limbs, R = 2^392, fp.py module docstring).  The input
fold pipeline is REIMPLEMENTED here rather than calling fp's helpers:
pallas rejects kernel bodies that capture constants, and fp's folds close
over the R392/R400 wrap arrays — so those constants are threaded in as
refs instead.  The division by R is fp's own `_exact_div_R`, which
captures no array.  Drift between the two copies is caught by the
bit-equality asserts in tests/test_pallas_fp.py (full pipeline, multiple
tile shapes and edge values).  Only the column contraction intentionally
differs: f32 dots against constant gather matrices (the MXU-friendly
form; `fp._mul_cols_shift`'s reshape trick exists to keep the *XLA
graph* small, which is irrelevant within a single fused kernel) — exact,
so bit-identity still holds.  The f32 exactness argument is fp.py's:
compressed limbs <= 510, products < 2^18, 49-term sums < 2^24.

Status: bit-identical to `fp.mont_mul` in interpreter mode, and lowers
for v5e through Mosaic (tests/test_tpu_compile.py); never run on a chip.
Opt-in via bench.py's kernel candidates until profiled.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import fp

NLIMB = fp.NLIMB      # 49
LB = fp.LB            # 8

# contraction matrices as f32 constants (antidiagonal gather, fp._diag_mat)
_DIAG2N = fp._diag_mat()                  # (2N, N^2)
_DIAGN = fp._diag_mat()[:NLIMB]           # (N, N^2)

TILE = 256  # batch elements per grid step


MASK = int(fp.MASK)


def _mont_body(a, b, d2n, dn, npl, pconst, r392c, r400c):
    """The fused SOS Montgomery multiply on plain arrays (N, T) — shared
    by the one-shot kernel and the CHAIN kernel (state held in VMEM
    across iterations; the byte-wall experiment)."""
    r392 = r392c[:, None]
    r400 = r400c[:, None]

    z1 = jnp.zeros((1, a.shape[1]), jnp.int32)
    z2 = jnp.zeros((2, a.shape[1]), jnp.int32)

    def fold_w(c):
        lo = c & MASK
        hi = c >> LB
        return lo + jnp.concatenate([z1, hi[:-1]], axis=0) + hi[-1:] * r392

    def fold3_w(c):
        b0 = c & MASK
        b1 = (c >> LB) & MASK
        b2 = c >> (2 * LB)
        out = (
            b0
            + jnp.concatenate([z1, b1[:-1]], axis=0)
            + jnp.concatenate([z2, b2[:-2]], axis=0)
        )
        # (1, T) row slices, never int indices: Mosaic has no
        # dynamic_slice, which an int index lowers to
        spill392 = b1[-1:] + b2[-2:-1]
        return out + spill392 * r392 + b2[-1:] * r400

    def compress(c):
        return fold_w(fold_w(fold3_w(c)))

    def fold3_trunc(c, n_out):
        b0 = c & MASK
        b1 = (c >> LB) & MASK
        b2 = c >> (2 * LB)
        s1 = jnp.concatenate([z1, b1[: n_out - 1]], axis=0)
        s2 = jnp.concatenate([z2, b2[: n_out - 2]], axis=0)
        return b0[:n_out] + s1 + s2

    def fold_trunc(c, n_out):
        lo = c & MASK
        hi = c >> LB
        sh = jnp.concatenate([z1, hi[: n_out - 1]], axis=0)
        return lo[:n_out] + sh

    def compress_mod_R(c):
        return fold_trunc(fold3_trunc(c, NLIMB), NLIMB)

    def cols(x, y, d):
        prods = (x[:, None, :] * y[None, :, :]).reshape(NLIMB * NLIMB, -1)
        return lax.dot(d, prods, precision=lax.Precision.HIGHEST)

    ar = compress(a).astype(jnp.float32)
    br = compress(b).astype(jnp.float32)
    cols_t = cols(ar, br, d2n).astype(jnp.int32)          # (2N, T)
    t_red = compress_mod_R(cols_t[:NLIMB])
    np_f = jnp.broadcast_to(npl.astype(jnp.float32)[:, None], a.shape)
    m_red = compress_mod_R(
        cols(t_red.astype(jnp.float32), np_f, dn).astype(jnp.int32)
    )
    p_f = jnp.broadcast_to(pconst.astype(jnp.float32)[:, None], a.shape)
    u = cols(m_red.astype(jnp.float32), p_f, d2n).astype(jnp.int32) + cols_t
    return fp._exact_div_R(u)


def _mont_mul_kernel(
    a_ref, b_ref, d2n_ref, dn_ref, np_ref, p_ref, r392_ref, r400_ref, out_ref
):
    """One tile: a, b (N, TILE) i32 lazy -> out (N, TILE) i32 lazy.

    Bit-for-bit mirror of fp.mont_mul: _compress_limbs on both operands,
    cols_t, t mod R, m = t*(-p^-1) mod R, u = m*p + t, u / R.
    """
    out_ref[:] = _mont_body(
        a_ref[:], b_ref[:], d2n_ref[:], dn_ref[:], np_ref[:], p_ref[:],
        r392_ref[:], r400_ref[:])


def _mont_chain_kernel(steps):
    def kernel(a_ref, b_ref, d2n_ref, dn_ref, np_ref, p_ref, r392_ref,
               r400_ref, out_ref):
        b = b_ref[:]
        d2n, dn = d2n_ref[:], dn_ref[:]
        npl, pconst = np_ref[:], p_ref[:]
        r392c, r400c = r392_ref[:], r400_ref[:]

        def body(_, x):
            return _mont_body(x, b, d2n, dn, npl, pconst, r392c, r400c)

        out_ref[:] = lax.fori_loop(0, steps, body, a_ref[:])

    return kernel


def mont_mul_pallas(a, b, interpret=False):
    """Drop-in fused `fp.mont_mul` — one pallas_call per TILE-wide slab.

    a, b: (NLIMB, B) int32 lazily-reduced Montgomery operands (any values
    within fp.mont_mul's contract).
    """
    from jax.experimental import pallas as pl

    orig_shape = a.shape
    a2 = a.reshape(NLIMB, -1)
    b2 = jnp.broadcast_to(b, orig_shape).reshape(NLIMB, -1)
    n = a2.shape[1]
    pad = (-n) % TILE
    if pad:
        a2 = jnp.pad(a2, ((0, 0), (0, pad)))
        b2 = jnp.pad(b2, ((0, 0), (0, pad)))
    total = a2.shape[1]

    out = pl.pallas_call(
        _mont_mul_kernel,
        out_shape=jax.ShapeDtypeStruct((NLIMB, total), jnp.int32),
        grid=(total // TILE,),
        in_specs=[
            pl.BlockSpec((NLIMB, TILE), lambda i: (0, i)),
            pl.BlockSpec((NLIMB, TILE), lambda i: (0, i)),
            pl.BlockSpec((2 * NLIMB, NLIMB * NLIMB), lambda i: (0, 0)),
            pl.BlockSpec((NLIMB, NLIMB * NLIMB), lambda i: (0, 0)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((NLIMB, TILE), lambda i: (0, i)),
        interpret=interpret,
    )(
        a2,
        b2,
        jnp.asarray(_DIAG2N),
        jnp.asarray(_DIAGN),
        jnp.asarray(fp.NPRIME_LIMBS),
        jnp.asarray(fp.P_LIMBS),
        jnp.asarray(fp.R392_LIMBS),
        jnp.asarray(fp.R400_LIMBS),
    )
    if pad:
        out = out[:, :n]
    return out.reshape(orig_shape)


def mont_chain_pallas(a, b, steps, interpret=False):
    """x <- mont_mul(x, b), `steps` times, as ONE pallas_call: the chain
    state never leaves VMEM between iterations.  This is the byte-wall
    experiment — against `mont_chain_xla` (same chain
    as `steps` separate XLA ops, HBM round-trip per step) the ratio
    directly measures what pairing-layer fusion buys."""
    from jax.experimental import pallas as pl

    orig_shape = a.shape
    a2 = a.reshape(NLIMB, -1)
    b2 = jnp.broadcast_to(b, orig_shape).reshape(NLIMB, -1)
    n = a2.shape[1]
    pad = (-n) % TILE
    if pad:
        a2 = jnp.pad(a2, ((0, 0), (0, pad)))
        b2 = jnp.pad(b2, ((0, 0), (0, pad)))
    total = a2.shape[1]

    out = pl.pallas_call(
        _mont_chain_kernel(steps),
        out_shape=jax.ShapeDtypeStruct((NLIMB, total), jnp.int32),
        grid=(total // TILE,),
        in_specs=[
            pl.BlockSpec((NLIMB, TILE), lambda i: (0, i)),
            pl.BlockSpec((NLIMB, TILE), lambda i: (0, i)),
            pl.BlockSpec((2 * NLIMB, NLIMB * NLIMB), lambda i: (0, 0)),
            pl.BlockSpec((NLIMB, NLIMB * NLIMB), lambda i: (0, 0)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
            pl.BlockSpec((NLIMB,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((NLIMB, TILE), lambda i: (0, i)),
        interpret=interpret,
    )(
        a2,
        b2,
        jnp.asarray(_DIAG2N),
        jnp.asarray(_DIAGN),
        jnp.asarray(fp.NPRIME_LIMBS),
        jnp.asarray(fp.P_LIMBS),
        jnp.asarray(fp.R392_LIMBS),
        jnp.asarray(fp.R400_LIMBS),
    )
    if pad:
        out = out[:, :n]
    return out.reshape(orig_shape)


def mont_chain_xla(a, b, steps):
    """The same chain as separate fp.mont_mul XLA ops (fusion baseline)."""
    return lax.fori_loop(0, steps, lambda _, x: fp.mont_mul(x, b), a)
