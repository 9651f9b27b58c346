"""Multi-scalar multiplication by bit-planes (device tier).

The grouped batch-verification equation needs Σ_i k_i·P_i for 32-bit
scalars k_i — per root-group on the pubkey side, globally on the signature
side (SURVEY §2.3 "aggregate-pubkey G1 MSM as vmap'd XLA kernels";
reference analog: blst's per-set jacobian pubkey aggregation,
`chain/bls/utils.ts:5-16`, lifted to whole-batch scale).

Per-lane double-and-add ladders cost 2·nbits point ops per POINT. Here the
sum is decomposed by bit-plane instead:

    Σ_i k_i·P_i = Σ_b 2^b · U_b,   U_b = Σ_{i: bit b of k_i} P_i

and each U_b is a masked sum — nbits point ops per point, with two more
structural wins on top:

- subset-4 sharing: lanes are grouped in fours and all 16 subset sums of
  each group are precomputed ONCE (11 adds per group, shared by every
  bit-plane); a plane then gathers its subset by the 4-bit mask and
  tree-reduces over groups. Per-plane work drops from L−1 to L/4 adds.
- the power-of-two recombination (Σ 2^b·U_b) is the CALLER's problem —
  the batch verifier never materializes it, pairing each U_b against a
  precomputed −[2^b]g1 constant instead (`points.NEG_G1_POW2_*`), or
  Horner-combining across lanes where it must (per-root pubkey sums).

Everything is static-shape, branch-free, and generic over the coordinate
field via `CurveOps` (G1 and G2 alike).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _bsl(curve, a, sl):
    """Slice the last batch axis (the one just before the coord axes)."""
    return a[(Ellipsis, sl) + (slice(None),) * curve.coord_ndim]


def tree_sum(curve, p):
    """log-depth complete-add reduction over the last batch axis.

    p: (X, Y, Z) projective with shape (..., n, *coord) → (..., *coord).
    The levels run as one loop over a fixed shape (n padded to a power
    of two with infinity, each level's sums refilled to full width with
    it): the point addition is compiled once, not once per level.
    """
    cn = curve.coord_ndim
    n = p[0].shape[-1 - cn]
    m = 1 << max(0, (n - 1).bit_length())
    axis = p[0].ndim - 1 - cn
    batch = p[0].shape[:axis]
    if m > n:
        pad = curve.infinity(batch + (m - n,))
        p = tuple(jnp.concatenate([c, f], axis) for c, f in zip(p, pad))
    if m > 1:
        fill = curve.infinity(batch + (m // 2,))

        def level(_, x):
            s = curve.add(
                tuple(_bsl(curve, c, slice(0, None, 2)) for c in x),
                tuple(_bsl(curve, c, slice(1, None, 2)) for c in x),
            )
            return tuple(jnp.concatenate([c, f], axis) for c, f in zip(s, fill))

        p = lax.fori_loop(0, m.bit_length() - 1, level, p)
    return tuple(_bsl(curve, c, 0) for c in p)


def subset_table4(curve, p4):
    """All 16 subset sums of 4 projective points.

    p4: (..., 4, *coord) → (..., 16, *coord); entry m sums the lanes whose
    bit is set in m (entry 0 = infinity). 11 complete adds in 3 stacked
    calls (6 pairs, 4 triples, 1 quad) — shared by every bit-plane that
    gathers from the table.
    """
    cn = curve.coord_ndim
    pt = [tuple(_bsl(curve, c, k) for c in p4) for k in range(4)]

    def stk(pts):
        return tuple(jnp.stack([q[i] for q in pts], axis=0) for i in range(3))

    def unstk(s, k):
        return tuple(c[k] for c in s)

    # pairs: 0+1, 0+2, 1+2, 0+3, 1+3, 2+3
    pr = curve.add(
        stk([pt[0], pt[0], pt[1], pt[0], pt[1], pt[2]]),
        stk([pt[1], pt[2], pt[2], pt[3], pt[3], pt[3]]),
    )
    p01, p02, p12, p03, p13, p23 = (unstk(pr, k) for k in range(6))
    # triples: 0+1+2, 0+1+3, 0+2+3, 1+2+3
    tr = curve.add(
        stk([p01, p01, p02, p12]), stk([pt[2], pt[3], pt[3], pt[3]])
    )
    t012, t013, t023, t123 = (unstk(tr, k) for k in range(4))
    # quad
    quad = curve.add(t012, pt[3])

    inf = curve.infinity(pt[0][0].shape[: pt[0][0].ndim - cn])
    entries = [
        inf, pt[0], pt[1], p01, pt[2], p02, p12, t012,
        pt[3], p03, p13, t013, p23, t023, t123, quad,
    ]
    return tuple(
        jnp.stack([e[i] for e in entries], axis=-1 - cn) for i in range(3)
    )


def masked_plane_sums(curve, p, bits):
    """Per-bit-plane masked sums: U_t = Σ_l bits[..., l, t]·P_l.

    p: projective (..., L, *coord), L % 4 == 0; bits: (..., L, T) in {0,1}.
    Returns (T, ..., *coord) projective — plane axis LEADING so callers
    can scan/slice it.
    """
    cn = curve.coord_ndim
    L = p[0].shape[-1 - cn]
    T = bits.shape[-1]
    batch = p[0].shape[: -1 - cn]
    G = L // 4
    p4 = tuple(c.reshape(batch + (G, 4) + c.shape[-cn:]) for c in p)
    table = subset_table4(curve, p4)  # (..., G, 16, *coord)
    # 4-bit subset index per (group, plane)
    b4 = bits.reshape(batch + (G, 4, T))
    idx = (
        b4[..., 0, :] + (b4[..., 1, :] << 1) + (b4[..., 2, :] << 2)
        + (b4[..., 3, :] << 3)
    )  # (..., G, T)
    planes = tuple(
        jnp.take_along_axis(
            c, idx.reshape(idx.shape + (1,) * cn), axis=-1 - cn
        )
        for c in table
    )  # (..., G, T, *coord)
    # plane axis to the front, keep G last for the tree
    planes = tuple(jnp.moveaxis(c, -1 - cn, 0) for c in planes)  # (T,...,G,)
    return tree_sum(curve, planes)  # (T, ..., *coord)


def horner_pow2(curve, planes):
    """Σ_t 2^t · planes[t] over the LEADING plane axis (LSB first).

    31 doublings + 32 complete adds as one lax.scan — used where the
    power-of-two recombination cannot ride constant Miller lanes (the
    per-root pubkey sums, which pair against variable H(m) points).
    Vectorize the trailing batch axes to amortize the sequential depth.
    """
    cn = curve.coord_ndim
    batch = planes[0].shape[1 : planes[0].ndim - cn]
    xs = tuple(jnp.flip(c, axis=0) for c in planes)  # MSB first

    def step(acc, plane):
        return curve.add(curve.double(acc), plane), None

    acc, _ = lax.scan(step, curve.infinity(batch), xs)
    return acc
