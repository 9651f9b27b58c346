"""Elliptic-curve point ops on homogeneous projective coordinates (device).

Generic over the coordinate field: the same code drives G1 (field = ops.fp,
shapes (..., 32)) and G2 (field = ops.fp2, shapes (..., 2, 32)). Points are
(X, Y, Z) tuples with the curve's affine point (X/Z, Y/Z); infinity is
(0, 1, 0), representable and handled by the COMPLETE addition formulas of
Renes–Costello–Batina 2016 (a = 0 case) — no branches, no special cases, so
everything vmaps and shards cleanly. This replaces the reference's jacobian
add/dbl branching inside blst (SURVEY.md §2.3: `@chainsafe/blst` point ops).

Scalar multiplication is a fixed-trip MSB-first double-and-add `lax.scan`
over a bit vector — data-independent control flow, batchable over both
points and scalars (the random-coefficient batch-verify path,
reference: blst verifyMultipleSignatures' rand-scaling).
"""

from __future__ import annotations


import jax.numpy as jnp
from jax import lax

from ..bls import curve as _curve
from ..bls import fields as _fields
from . import fp, fp2
from .io_host import fq2_to_limbs, fq_to_limbs


class CurveOps:
    """Point arithmetic for one curve over field module `F`.

    `b3` is 3·b (curve constant) as a field limb array; `coord_ndim` is the
    number of trailing axes of one coordinate (1 for Fp, 2 for Fp2).
    """

    def __init__(self, F, b3, coord_ndim: int):
        self.F = F
        self.b3 = b3
        self.coord_ndim = coord_ndim

    # -- constructors -------------------------------------------------------

    def infinity(self, batch: tuple = ()):
        return (self.F.zero(batch), self.F.one(batch), self.F.zero(batch))

    def from_affine(self, x, y):
        batch = x.shape[: x.ndim - self.coord_ndim]
        return (x, y, self.F.one(batch))

    # -- predicates ---------------------------------------------------------

    def is_infinity(self, p):
        return self.F.is_zero(p[2])

    def eq(self, p, q):
        """Projective equality: X1·Z2 == X2·Z1 and Y1·Z2 == Y2·Z1 (plus
        matching infinity flags)."""
        x1, y1, z1 = p
        x2, y2, z2 = q
        cross_x = self.F.eq(self.F.mul(x1, z2), self.F.mul(x2, z1))
        cross_y = self.F.eq(self.F.mul(y1, z2), self.F.mul(y2, z1))
        inf1, inf2 = self.is_infinity(p), self.is_infinity(q)
        both_inf = inf1 & inf2
        return both_inf | (cross_x & cross_y & ~inf1 & ~inf2)

    def select(self, cond, p, q):
        s = self.F.select
        return (s(cond, p[0], q[0]), s(cond, p[1], q[1]), s(cond, p[2], q[2]))

    # -- group law (complete, branchless) -----------------------------------
    #
    # LATENCY DISCIPLINE (round-2 profile of the ladders): the
    # scalar ladders are latency-bound — at 4096 lanes each Montgomery
    # multiply's sequential cost dominates, and wider stacked multiplies
    # are ~4× cheaper per lane. So every formula below evaluates its
    # INDEPENDENT products as ONE stacked F.mul call: RCB16 addition runs
    # as 3 stacked calls (6+2+6 products) instead of 14 sequential ones,
    # doubling as 3 (4+1+4) instead of ~10.

    def _mulstack(self, lhs, rhs):
        """One stacked field multiply over a new leading axis (operands
        broadcast to a common shape first — constants like b3 ride along)."""
        F = self.F
        shape = jnp.broadcast_shapes(*(a.shape for a in lhs), *(b.shape for b in rhs))
        lhs = [jnp.broadcast_to(a, shape) for a in lhs]
        rhs = [jnp.broadcast_to(b, shape) for b in rhs]
        out = F.mul(jnp.stack(lhs, axis=0), jnp.stack(rhs, axis=0))
        return [out[i] for i in range(len(lhs))]

    def _addstack(self, exprs):
        """One stacked `F.reduce_sums` over a stage's independent add/sub
        COLUMN expressions (each with value < 4p) — the add-side analog
        of `_mulstack` (one carry scan instead of one per value)."""
        shape = jnp.broadcast_shapes(*(e.shape for e in exprs))
        out = self.F.reduce_sums(
            jnp.stack([jnp.broadcast_to(e, shape) for e in exprs], axis=0)
        )
        return [out[i] for i in range(len(exprs))]

    def add(self, p, q):
        """RCB16 Algorithm 7 (a=0): complete projective addition.

        Stacked-scan discipline on BOTH op kinds: 3 stacked multiplies
        (6+2+6 products) and 4 stacked add-scans — round 4 paid ~15
        individual add scans on top of the multiplies."""
        F, b3 = self.F, self.b3
        TP = F.TWO_P
        x1, y1, z1 = p
        x2, y2, z2 = q
        xy1, yz1, xz1, xy2, yz2, xz2 = self._addstack(
            [x1 + y1, y1 + z1, x1 + z1, x2 + y2, y2 + z2, x2 + z2]
        )
        # stage A: all 6 cross products at once
        t0, t1, t2, u, v, w = self._mulstack(
            [x1, y1, z1, xy1, yz1, xz1], [x2, y2, z2, xy2, yz2, xz2]
        )
        s01, s12, s02, t00 = self._addstack(
            [t0 + t1, t1 + t2, t0 + t2, t0 + t0]
        )
        t3, t4, y3p, x3 = self._addstack(
            [u - s01 + TP, v - s12 + TP, w - s02 + TP, t00 + t0]
        )
        # stage B: the two b3 scalings
        t2b, y3 = self._mulstack([b3, b3], [t2, y3p])
        z3, t1 = self._addstack([t1 + t2b, t1 - t2b + TP])
        # stage C: the 6 output products
        a, b, c, d, e, f = self._mulstack(
            [t3, t4, y3, t1, z3, x3], [t1, y3, x3, z3, t4, t3]
        )
        ox, oy, oz = self._addstack([a - b + TP, c + d, e + f])
        return (ox, oy, oz)

    def add_mixed(self, p, q_affine):
        """RCB16 Algorithm 8 (a=0): complete mixed addition, Z2 = 1.

        NOTE: the affine operand cannot encode infinity; callers mask
        degenerate inputs at the API layer.
        """
        F, b3 = self.F, self.b3
        TP = F.TWO_P
        x1, y1, z1 = p
        x2, y2 = q_affine
        xy1, xy2 = self._addstack([x1 + y1, x2 + y2])
        # stage A: cross products + the b3·z1 scaling are all independent
        t0, t1, u, xz, yz, t2b = self._mulstack(
            [x1, y1, xy1, x2, y2, b3], [x2, y2, xy2, z1, z1, z1]
        )
        s01, t00, y3p, t4, z3, t1m = self._addstack(
            [t0 + t1, t0 + t0, xz + x1, yz + y1, t1 + t2b, t1 - t2b + TP]
        )
        t3, x3 = self._addstack([u - s01 + TP, t00 + t0])
        t1 = t1m
        # stage B: b3 scaling of y3p
        y3 = F.mul(b3, y3p)
        # stage C: outputs
        a, b, c, d, e, f = self._mulstack(
            [t3, t4, y3, t1, z3, x3], [t1, y3, x3, z3, t4, t3]
        )
        ox, oy, oz = self._addstack([a - b + TP, c + d, e + f])
        return (ox, oy, oz)

    def double(self, p):
        """RCB16 Algorithm 9 (a=0): complete projective doubling."""
        F, b3 = self.F, self.b3
        TP = F.TWO_P
        x, y, z = p
        # stage A: the 4 independent squares/products
        t0, t1, t2, txy = self._mulstack([y, y, z, x], [y, z, z, y])
        # stage B: b3·z²
        t2b = F.mul(b3, t2)
        z2d, y3s, t1c = self._addstack([t0 + t0, t0 + t2b, t2b + t2b])
        z4d, t2c = self._addstack([z2d + z2d, t1c + t2b])
        z8, t0c = self._addstack([z4d + z4d, t0 - t2c + TP])  # z8 = 8y²
        # stage C: the 4 output products
        x3, z3, y3, xt = self._mulstack(
            [t2b, t1, t0c, t0c], [z8, z8, y3s, txy]
        )
        oy, ox = self._addstack([x3 + y3, xt + xt])
        return (ox, oy, z3)

    def neg(self, p):
        return (p[0], self.F.neg(p[1]), p[2])

    # -- scalar multiplication ---------------------------------------------

    def scalar_mul_bits(self, bits, q_affine):
        """[k]Q for Q affine, k given as (..., nbits) int32 bits (MSB first).

        MSB-first double-and-add over a fixed-trip scan; the conditional add
        is a select, so batched scalars (vmap over sets) cost the same as
        uniform ones — the batch is where the parallelism lives.
        """
        nbits = bits.shape[-1]
        batch = jnp.broadcast_shapes(
            bits.shape[:-1], q_affine[0].shape[: q_affine[0].ndim - self.coord_ndim]
        )
        xq = jnp.broadcast_to(
            q_affine[0], batch + q_affine[0].shape[q_affine[0].ndim - self.coord_ndim:]
        )
        yq = jnp.broadcast_to(
            q_affine[1], batch + q_affine[1].shape[q_affine[1].ndim - self.coord_ndim:]
        )
        bits_t = jnp.moveaxis(jnp.broadcast_to(bits, batch + (nbits,)), -1, 0)

        def step(acc, bit):
            acc = self.double(acc)
            added = self.add_mixed(acc, (xq, yq))
            acc = self.select(bit != 0, added, acc)
            return acc, None

        acc, _ = lax.scan(step, self.infinity(batch), bits_t)
        return acc

    def scalar_mul_windowed(self, bits, q_affine, window: int = 4):
        """[k]Q via fixed 2^w windows: same contract as `scalar_mul_bits`
        with ~half the group additions for 64-bit scalars.

        MEASURED NEGATIVE RESULT on v5e (round 2, tools/win_check.py):
        despite the op-count win, this runs SLOWER than the bit ladder
        (G2 @512 lanes: 307 vs 262 ms) — the 2^w per-lane table selects
        (16 vectorized where()s per window) outweigh the saved mixed
        adds, and XLA compile time grows ~30x (the unrolled table build
        + select trees). Kept as a pinned, differential-tested option;
        the verifier kernels use `scalar_mul_bits`.

        Per window step: w doublings + ONE complete addition of the
        table entry T[digit] (T = [0·Q .. (2^w−1)·Q], 2^w−2 mixed adds
        to build, amortized over the whole batch's scan). The per-lane
        table lookup is 2^w field selects — noise next to a group add.
        Complete formulas make the digit-0 case uniform (adds the
        identity), so the scan body is branch-free like the bit ladder.
        """
        nbits = bits.shape[-1]
        if nbits % window != 0:
            return self.scalar_mul_bits(bits, q_affine)
        batch = jnp.broadcast_shapes(
            bits.shape[:-1], q_affine[0].shape[: q_affine[0].ndim - self.coord_ndim]
        )
        coord = q_affine[0].shape[q_affine[0].ndim - self.coord_ndim :]
        xq = jnp.broadcast_to(q_affine[0], batch + coord)
        yq = jnp.broadcast_to(q_affine[1], batch + coord)
        bits = jnp.broadcast_to(bits, batch + (nbits,))

        # digits, MSB-first: (n_windows, ...batch)
        weights = jnp.asarray([1 << (window - 1 - i) for i in range(window)])
        digits = jnp.moveaxis(
            jnp.sum(bits.reshape(batch + (nbits // window, window)) * weights, -1),
            -1,
            0,
        )

        # table T[d] = d·Q as stacked projective coords, axis 0 = digit
        entries = [self.infinity(batch), self.from_affine(xq, yq)]
        for _ in range(2, 1 << window):
            entries.append(self.add_mixed(entries[-1], (xq, yq)))
        table = tuple(
            jnp.stack([e[i] for e in entries], axis=0) for i in range(3)
        )

        def lookup(digit):
            cond = lambda d: digit == d  # noqa: E731
            out = tuple(t[0] for t in table)
            for d in range(1, 1 << window):
                out = self.select(cond(d), tuple(t[d] for t in table), out)
            return out

        def step(acc, digit):
            for _ in range(window):
                acc = self.double(acc)
            return self.add(acc, lookup(digit)), None

        acc, _ = lax.scan(step, self.infinity(batch), digits)
        return acc

    # -- normalization ------------------------------------------------------

    def to_affine(self, p):
        """(X/Z, Y/Z); infinity maps to (0, 0) — mask via is_infinity."""
        zinv = self.F.inv(p[2])
        return (self.F.mul(p[0], zinv), self.F.mul(p[1], zinv))


# --- curve instances -------------------------------------------------------

def _b3_g1():
    return jnp.asarray(fq_to_limbs(_fields.Fq(12)))  # 3·4


def _b3_g2():
    # 3·4(1+u) = 12 + 12u
    return jnp.asarray(fq2_to_limbs(_fields.Fq2.from_ints(12, 12)))


g1 = CurveOps(fp, _b3_g1(), coord_ndim=1)
g2 = CurveOps(fp2, _b3_g2(), coord_ndim=2)

# Generators as affine limb constants (host-computed from the oracle)
_g1_gen = _curve.PointG1.generator().to_affine()
_g2_gen = _curve.PointG2.generator().to_affine()
G1_GEN_X = jnp.asarray(fq_to_limbs(_g1_gen[0]))
G1_GEN_Y = jnp.asarray(fq_to_limbs(_g1_gen[1]))
G2_GEN_X = jnp.asarray(fq2_to_limbs(_g2_gen[0]))
G2_GEN_Y = jnp.asarray(fq2_to_limbs(_g2_gen[1]))


# --- ψ endomorphism on G2 (device tier) ------------------------------------
#
# ψ = untwist∘Frobenius∘twist acts on G2 as multiplication by the BLS
# parameter z = X_PARAM (since p ≡ t−1 = z mod r): ψ(x, y) =
# (c_x·conj(x), c_y·conj(y)) with the oracle's Budroni–Pintore constants
# (bls/curve.py psi()). The grouped batch verifier splits its 64-bit
# random coefficients as r = a + z·b (a, b 32-bit — still 2^-64 sound:
# (a, b) ↦ a + z·b is injective, so r is uniform over 2^64 residues) and
# trades half of every scalar-combination for one ψ application: 2 fp2
# multiplies instead of 32 doubling steps.

_PSI_CX_L = jnp.asarray(fq2_to_limbs(_curve._PSI_CX))
_PSI_CY_L = jnp.asarray(fq2_to_limbs(_curve._PSI_CY))


def g2_psi(p):
    """ψ of a projective G2 point: (c_x·conj(X), c_y·conj(Y), conj(Z)).

    Conjugation commutes with the projective quotient (it is Fp-linear),
    so infinity maps to infinity and no normalization is needed."""
    x, y, z = p
    out = fp2.mul(
        jnp.stack([fp2.conj(x), fp2.conj(y)], axis=0),
        jnp.stack(
            [
                jnp.broadcast_to(_PSI_CX_L, x.shape),
                jnp.broadcast_to(_PSI_CY_L, y.shape),
            ],
            axis=0,
        ),
    )
    return (out[0], out[1], fp2.conj(z))


def _neg_g1_pow2_table(nbits: int):
    """Affine limb table of −[2^b]·g1, b = 0..nbits−1 (host-computed).

    The grouped verifier's signature aggregate rides constant-G1 Miller
    lanes: e(−g1, Σ 2^b·U_b) = Π_b e(−[2^b]g1, U_b), so the per-bit plane
    sums never need a sequential Horner combine on device."""
    import numpy as np

    xs, ys = [], []
    cur = _curve.PointG1.generator()
    for _ in range(nbits):
        aff = cur.to_affine()
        xs.append(fq_to_limbs(aff[0]))
        ys.append(fq_to_limbs(-aff[1]))
        cur = cur.double()
    return jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))


# 64 entries: the per-set kernel's signature aggregate uses full 64-bit
# random coefficients (no GLS split) and needs −[2^b]g1 for b = 0..63;
# the grouped kernel's 32-bit halves use the prefix
NEG_G1_POW2_64_X, NEG_G1_POW2_64_Y = _neg_g1_pow2_table(64)
NEG_G1_POW2_X, NEG_G1_POW2_Y = NEG_G1_POW2_64_X[:32], NEG_G1_POW2_64_Y[:32]
