"""Fp12 = Fp6[w]/(w² − v) on int32 limb vectors (device tier).

Element shape: (..., 2, 3, 2, 32) — axis -4 indexes (c0, c1) of c0 + c1·w.
A full multiplication is 3 Fp6 products stacked into ONE fp6.mul call
(= 54 Fp products in a single Montgomery scan). The pairing's line update
uses the sparse `mul_by_line` (15 Fp2 products) instead of a full mul.

Frobenius maps use the flattened Fq2[w]/(w⁶ − ξ) view with γ constants
computed once on the host by the oracle (`bls.fields._FROB_GAMMA`).

Oracle: `lodestar_tpu/bls/fields.Fq12`.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..bls import fields as _f
from . import fp, fp2, fp6
from .limbs import N_LIMBS, fp_to_mont_host


def _split(a):
    return a[..., 0, :, :, :], a[..., 1, :, :, :]


def _join(c0, c1):
    return jnp.stack([c0, c1], axis=-4)


def _bcast(a, b):
    batch = jnp.broadcast_shapes(a.shape[:-4], b.shape[:-4])
    return (
        jnp.broadcast_to(a, batch + a.shape[-4:]),
        jnp.broadcast_to(b, batch + b.shape[-4:]),
    )


def add(a, b):
    return fp.add(a, b)


def mul(a, b):
    """Karatsuba over w: c0 = v0 + v·v1, c1 = (a0+a1)(b0+b1) − v0 − v1.

    Both the operand sums and the interpolation run as single
    bounds-tracked combine scans (fp.reduce_stack) — the add-side analog
    of stacking the three Fp6 products into one multiply."""
    a, b = _bcast(a, b)
    a0, a1 = _split(a)
    b0, b1 = _split(b)
    sa, sb = fp.reduce_sums(jnp.stack([a0 + a1, b0 + b1]))
    v = fp6.mul(jnp.stack([a0, a1, sa], axis=0), jnp.stack([b0, b1, sb], axis=0))
    v0, v1, v01 = v[0], v[1], v[2]
    W = fp.wrap
    c0 = W(v0) + fp6.mul_by_v_s(W(v1))
    c1 = W(v01) - W(v0) - W(v1)
    c0, c1 = fp.reduce_stack([c0, c1])
    return _join(c0, c1)


def square(a):
    """Complex squaring: c0 = (a0+a1)(a0+v·a1) − v0 − v·v0, c1 = 2v0."""
    a0, a1 = _split(a)
    W = fp.wrap
    s0, s1 = fp.reduce_stack(
        [W(a0) + W(a1), W(a0) + fp6.mul_by_v_s(W(a1))]
    )
    v = fp6.mul(jnp.stack([a0, s0], axis=0), jnp.stack([a1, s1], axis=0))
    v0, mixed = v[0], v[1]
    c0 = W(mixed) - W(v0) - fp6.mul_by_v_s(W(v0))
    c1 = W(v0).double()
    c0, c1 = fp.reduce_stack([c0, c1])
    return _join(c0, c1)


def conj(a):
    """x^(p⁶): negate the w component."""
    a0, a1 = _split(a)
    return _join(a0, fp6.neg(a1))


def cyclotomic_square(g):
    """Granger–Scott squaring — valid ONLY for elements of the cyclotomic
    subgroup G_{Φ6}(Fp2) (anything after the final exponentiation's easy
    part). 9 Fp2 squarings in one stacked call vs the generic square's 12
    Fp2 products, and a flatter add tree.

    With c0 = (a, b, c), c1 = (d, e, f) over Fp2 (the three Fp4
    subalgebras (a,e), (c,d), (b,f) with y² = ξ):
        t0 = a² + ξe²   t6 = 2ae
        t2 = d² + ξc²   t7 = 2cd
        t4 = b² + ξf²   t8 = 2bf·ξ
        c0' = (3t0−2a, 3t2−2b, 3t4−2c)
        c1' = (3t8+2d, 3t6+2e, 3t7+2f)
    Differentially pinned against the oracle's generic square on
    cyclotomic inputs (tests/test_ops_pairing.py)."""
    g0, g1 = _split(g)
    a, b, c = fp6._split(g0)
    d, e, f = fp6._split(g1)
    W = fp.wrap
    sae, scd, sbf = fp.reduce_sums(jnp.stack([a + e, c + d, b + f]))
    lhs = jnp.stack([a, e, sae, c, d, scd, f, b, sbf], axis=0)
    s = fp2.mul(lhs, lhs)
    a2, e2, ae2, c2, d2, cd2, f2, b2, bf2 = (W(s[i]) for i in range(9))
    t6 = ae2 - a2 - e2  # 2ae
    t7 = cd2 - c2 - d2  # 2cd
    t8 = fp2.xi_s(bf2 - b2 - f2)  # 2bf·ξ
    t0 = fp2.xi_s(e2) + a2
    t2 = fp2.xi_s(c2) + d2
    t4 = fp2.xi_s(f2) + b2

    def three_t_minus_2x(t, x):
        return t.double() + t - W(x).double()

    def three_t_plus_2x(t, x):
        return t.double() + t + W(x).double()

    # the whole output assembly is ONE bounds-tracked combine scan
    c0 = fp6.join_s(
        three_t_minus_2x(t0, a),
        three_t_minus_2x(t2, b),
        three_t_minus_2x(t4, c),
    )
    c1 = fp6.join_s(
        three_t_plus_2x(t8, d),
        three_t_plus_2x(t6, e),
        three_t_plus_2x(t7, f),
    )
    c0, c1 = fp.reduce_stack([c0, c1])
    return _join(c0, c1)


def inv(a):
    """(c0 + c1w)⁻¹ = (c0 − c1w)/(c0² − v·c1²)."""
    a0, a1 = _split(a)
    sq = fp6.mul(jnp.stack([a0, a1], axis=0), jnp.stack([a0, a1], axis=0))
    denom = fp6.sub(sq[0], fp6.mul_by_v(sq[1]))
    dinv = fp6.inv(denom)
    out = fp6.mul(jnp.stack([a0, a1], axis=0), dinv[None])
    return _join(out[0], fp6.neg(out[1]))


def batch_inv(a):
    """Element-wise inverse over axis 0 via Montgomery's product trick:
    ONE tower inversion (a ~570-sequential-multiply Fermat chain) plus
    log-depth prefix/suffix product scans replaces n independent
    inversion chains —

        a_i⁻¹ = (Π_{j<i} a_j) · (Π_{j>i} a_j) · (Π_j a_j)⁻¹.

    The amortized entry behind `pairing.final_exponentiation_batch`
    (bisection probes share the easy part's inversion). All inputs must
    be nonzero — a single zero lane poisons the whole batch (the callers
    feed Miller-loop outputs and identity padding, never zero)."""
    n = a.shape[0]
    if n == 1:
        return inv(a)
    # inclusive prefix products, forward and reversed, in ONE loop
    both = _prefix_products(jnp.stack([a, jnp.flip(a, axis=0)], axis=1))
    inc, inc_rev = both[:, 0], both[:, 1]
    # exclusive prefix (identity-shifted) and exclusive suffix
    ident = one((1,) + a.shape[1:-4])
    pre = jnp.concatenate([ident, inc[:-1]], axis=0)
    suf = jnp.concatenate([jnp.flip(inc_rev, axis=0)[1:], ident], axis=0)
    total_inv = inv(inc[-1:])  # unit batch axis: see pairing._miller_loop_impl
    return mul(mul(pre, suf), total_inv)


def _prefix_products(a):
    """Inclusive prefix products over axis 0 in ceil(log2 n) steps of
    one full-width multiply each (Hillis–Steele: step k multiplies lane
    i by lane i − 2^k, the identity below lane 2^k). One loop over a
    fixed shape, so the multiply is compiled once, not once per level
    as `lax.associative_scan` would."""
    n = a.shape[0]
    lane = jnp.arange(n).reshape((n,) + (1,) * (a.ndim - 1))
    ident = one(a.shape[1:-4])

    def step(k, x):
        s = jnp.left_shift(1, k)
        shifted = jnp.where(lane >= s, jnp.roll(x, s, axis=0), ident)
        return mul(shifted, x)

    return lax.fori_loop(0, (n - 1).bit_length(), step, a)


def mul_by_line(f, l0, l1, l2):
    """f · (l0 + l1·w² + l2·w³), l_i ∈ Fp2 — the sparse pairing-line update.

    In tower coordinates the line is (A, B) with A = (l0, l1, 0),
    B = (0, l2, 0); Karatsuba needs f0·A, f1·B, (f0+f1)(A+B) where
    A+B = (l0, l1+l2, 0) — 15 Fp2 products in one stacked call.
    """
    f0, f1 = _split(f)
    f00, f01, f02 = fp6._split(f0)
    f10, f11, f12 = fp6._split(f1)
    W = fp.wrap
    g0, g1, g2, s = fp.reduce_sums(
        jnp.stack([f00 + f10, f01 + f11, f02 + f12, l1 + l2])
    )
    lhs = jnp.stack(
        [f00, f02, f00, f01, f01, f02, f12, f10, f11, g0, g2, g0, g1, g1, g2],
        axis=0,
    )
    rhs = jnp.stack(
        [l0, l1, l1, l0, l1, l0, l2, l2, l2, l0, s, s, l0, s, l0],
        axis=0,
    )
    rhs = jnp.broadcast_to(rhs, lhs.shape)
    p = fp2.mul(lhs, rhs)
    # t0 = f0·A, t1 = f1·B (B = l2·v), t2 = (f0+f1)(A+B) — then the
    # Karatsuba combine c0 = t0 + v·t1, c1 = t2 − t0 − t1, ALL as one
    # bounds-tracked scan (round 4 paid ~12 separate add scans here)
    t0 = fp6.join_s(
        W(p[0]) + fp2.xi_s(W(p[1])),
        W(p[2]) + W(p[3]),
        W(p[4]) + W(p[5]),
    )
    t1 = fp6.join_s(fp2.xi_s(W(p[6])), W(p[7]), W(p[8]))
    t2 = fp6.join_s(
        W(p[9]) + fp2.xi_s(W(p[10])),
        W(p[11]) + W(p[12]),
        W(p[13]) + W(p[14]),
    )
    c0 = t0 + fp6.mul_by_v_s(t1)
    c1 = t2 - t0 - t1
    c0, c1 = fp.reduce_stack([c0, c1])
    return _join(c0, c1)


# --- Frobenius -------------------------------------------------------------

def _gamma_const() -> np.ndarray:
    """(3, 6, 2, 32) Montgomery limbs: γ_i^(k) = ξ^(i(p^k−1)/6), k=1..3."""
    out = np.zeros((3, 6, 2, N_LIMBS), np.int32)
    for k in (1, 2, 3):
        for i, g in enumerate(_f._FROB_GAMMA[k]):
            out[k - 1, i, 0] = fp_to_mont_host(g.c0.n)
            out[k - 1, i, 1] = fp_to_mont_host(g.c1.n)
    return out


_GAMMA = _gamma_const()


def _to_w(a):
    """(..., 2, 3, 2, 32) tower layout → (..., 6, 2, 32) w-coefficients."""
    a0, a1 = _split(a)
    d = [
        a0[..., 0, :, :], a1[..., 0, :, :],
        a0[..., 1, :, :], a1[..., 1, :, :],
        a0[..., 2, :, :], a1[..., 2, :, :],
    ]
    return jnp.stack(d, axis=-3)


def _from_w(d):
    c0 = jnp.stack([d[..., 0, :, :], d[..., 2, :, :], d[..., 4, :, :]], axis=-3)
    c1 = jnp.stack([d[..., 1, :, :], d[..., 3, :, :], d[..., 5, :, :]], axis=-3)
    return _join(c0, c1)


def frobenius(a, power: int):
    """x^(p^power), power ∈ {1,2,3}: conj^power per w-coeff, then ·γ_i."""
    if power not in (1, 2, 3):
        raise ValueError("frobenius power must be 1, 2 or 3")
    d = _to_w(a)
    if power % 2 == 1:
        d = jnp.concatenate([d[..., 0:1, :], fp.neg(d[..., 1:2, :])], axis=-2)
    gammas = jnp.asarray(_GAMMA[power - 1])  # (6, 2, 32)
    d = fp2.mul(d, gammas)
    return _from_w(d)


def product_tree(fs):
    """log2-depth product over axis 0 (length static; empty → 1).

    Shared by pairing.pairing_check and the batch verifier — the reduction
    shape matters for device parallelism (sequential fold would serialize
    the whole batch). The levels run as ONE loop over a fixed shape (the
    batch padded to a power of two with the identity, each level's
    products refilled to full width with it), so the multiply is traced
    and compiled once instead of once per level: a TPU compile pays per
    multiply site, not per lane."""
    n = fs.shape[0]
    if n == 0:
        return one(fs.shape[1:-4])
    return product_levels(fs)[-1][0]


def product_levels(fs):
    """Every level of the log2-depth product tree over axis 0: a list
    whose entry j holds the m >> j partial products (m = the batch
    padded to a power of two with the identity; entry 0 is the padded
    leaves, the last entry the (1,) root). One traced multiply site."""
    n = fs.shape[0]
    batch = fs.shape[1:-4]
    m = 1 << max(0, (n - 1).bit_length())
    if m > n:
        fs = jnp.concatenate([fs, one((m - n,) + batch)], 0)
    depth = m.bit_length() - 1
    if depth == 0:
        return [fs]
    fill = one((m // 2,) + batch)

    def level(x, _):
        x = jnp.concatenate([mul(x[0::2], x[1::2]), fill], 0)
        return x, x

    _, stacked = lax.scan(level, fs, None, length=depth)
    return [fs] + [stacked[j, : m >> (j + 1)] for j in range(depth)]


def is_one(a):
    return eq(a, one(a.shape[:-4]))


def eq(a, b):
    return jnp.all(
        fp.canonical(a) == fp.canonical(b), axis=(-1, -2, -3, -4)
    )


def select(cond, a, b):
    return jnp.where(cond[..., None, None, None, None], a, b)


def zero(batch: tuple = ()):
    return jnp.zeros(batch + (2, 3, 2, N_LIMBS), jnp.int32)


def one(batch: tuple = ()):
    return _join(fp6.one(batch), fp6.zero(batch))
