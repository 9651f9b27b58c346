"""On-device G2 signature decompression + batched subgroup checking.

Removes the host's e2e floor (VERDICT r4 #5): the per-set work that kept
the chip underfed on few-core hosts was the C-tier signature decompression
(~0.6 ms/set: one Fp2 square root + a per-point ψ subgroup check). Both
move on-device here:

- **Decompression** (`decompress`): ZCash-format 96-byte compressed G2
  points are unpacked to 12-bit limbs by static byte gathers, validated
  (flags, coordinate range, curve membership), and the y coordinate is
  recovered by a branchless Fp2 square root (`fp2_sqrt`) using the complex
  method — two Fp exponentiations per lane, wide-batched, with the
  inverse obtained FREE from the same power chain (see below). The sign
  is selected by the compression flag.

- **Subgroup checking** (`planes_in_subgroup`): instead of a per-lane
  [x]-ladder, the verifier's EXISTING random bit-plane sums U_b are
  checked: ψ(U_b) == [x]·U_b for all 64 planes. ψ(P) = [x]P holds
  exactly on G2 (M. Scott, "A note on group membership tests for G1, G2
  and GT on BLS pairing-friendly curves", 2021 — the same endomorphism
  test the native C tier uses per point). Soundness of the batched form:
  write each accepted point S_i = g_i + h_i with g_i ∈ G2 and h_i in the
  complementary (cofactor) subgroup H — the decomposition exists and is
  endomorphism-stable because gcd(h2, r) = 1. ψ − [x] vanishes on G2 and
  is injective on H, so plane b passes iff Σ_{i: bit_b(r_i)} h_i = 0.
  For any fixed nonzero (h_i) vector a uniform mask zeroes the sum with
  probability ≤ 1/2 (condition on all bits but one at an index with
  h_i ≠ 0), and the 64 planes use independent bits ⇒ an out-of-subgroup
  signature survives with probability ≤ 2^-64 — the same bound as the
  verification equation itself, over the same randomness (union bound:
  total false-accept ≤ 2·2^-64).

Fp2 sqrt (p ≡ 3 mod 4), branchless complex method for c = c0 + c1·u:
    n  = c0² + c1²                     (norm; a QR in Fp whenever c is
                                        a square in Fp2)
    λ  = n^((p+1)/4)                   [Fp pow #1]  λ² == n else reject
    t  = (c0 + λ)/2
    u* = t^((p-3)/4)                   [Fp pow #2]
    e₀ = u*·t        (= t^((p+1)/4))
    χ  = u*·e₀       (= t^((p-1)/2) = ±1: the QR test, no third pow)
  χ = 1 (t is a QR):   y = e₀ + (c1/2)·u* · u        (1/e₀ = u*)
  χ = −1 (t non-QR):   y = −(c1/2)·u* + e₀·u
  The second branch works because e₀ = √(−t), u* = −1/e₀, and of the two
  candidate real parts (c0±λ)/2 exactly one is a QR (their product is
  −c1²/4, a non-residue) — all derived identities cost only multiplies,
  so the whole sqrt is TWO Fp pow chains + O(1) muls per lane. (Corner:
  c1 = 0 with c0 a non-residue would need √c0·u; the candidate then
  fails the final y² == c check and the lane reports invalid — honest
  signatures never land there, and the facade's per-set fallback keeps
  verdicts correct if an adversary crafts such an x.)

Reference analog: blst's POINTonE2_Uncompress + subgroup check as used by
the worker (`chain/bls/multithread/worker.ts:33-101` per SURVEY §2.2).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..bls.fields import P as _P_INT
from ..bls.fields import Fq
from . import fp, fp2
from .io_host import fq_to_limbs
from .limbs import N_LIMBS, P_LIMBS, int_to_limbs
from .pairing import _X_BITS_TAIL as _X_ABS_BITS_TAIL
from .points import g2, g2_psi

# --- constants --------------------------------------------------------------

_INV2 = jnp.asarray(fq_to_limbs(Fq(pow(2, -1, _P_INT))))  # Montgomery 1/2
_P_ARR = jnp.asarray(P_LIMBS)
# canonical c > (p-1)/2  ⟺  c >= (p+1)/2 (lex compare on limbs)
_HALF_P1 = jnp.asarray(int_to_limbs((_P_INT + 1) // 2))

_POW_SQRT = (_P_INT + 1) // 4
_POW_U = (_P_INT - 3) // 4

# byte→limb static gather: little-endian byte j holds bits 8j..8j+7 of the
# 384-bit coordinate; limb i holds bits 12i..12i+11
_IDX0 = np.array([(12 * i) // 8 for i in range(N_LIMBS)])
_SHIFT = np.array([(12 * i) % 8 for i in range(N_LIMBS)])
_IDX1 = _IDX0 + 1


def _bytes48_to_limbs(be_bytes):
    """(..., 48) uint8 big-endian → (..., 32) int32 canonical 12-bit limbs
    (normal domain, NOT Montgomery)."""
    le = jnp.flip(be_bytes.astype(jnp.int32), axis=-1)
    lo = jnp.take(le, jnp.asarray(_IDX0), axis=-1)
    # top limb's high byte would index past the end; bits there are zero
    hi = jnp.take(le, jnp.asarray(np.minimum(_IDX1, 47)), axis=-1)
    hi = jnp.where(jnp.asarray(_IDX1 < 48), hi, 0)
    sh = jnp.asarray(_SHIFT)
    return ((lo >> sh) + (hi << (8 - sh))) & 0xFFF


def _lex_lt_p(a):
    """a < p on canonical limb vectors."""
    return ~fp._lex_ge(a, _P_ARR)


def fp2_sqrt(c):
    """Branchless Fp2 square root (see module docstring).

    c: (..., 2, 32) Montgomery limbs. Returns (y, ok): y with y² == c when
    ok; ok False where c has no square root (or hits the c1=0 non-QR
    corner — callers treat either as an invalid encoding)."""
    c0 = c[..., 0, :]
    c1 = c[..., 1, :]
    sq = fp.mul(jnp.stack([c0, c1], 0), jnp.stack([c0, c1], 0))
    n = fp.add(sq[0], sq[1])
    lam = fp.pow_const(n, _POW_SQRT)
    lam_ok = fp.eq(fp.mul(lam, lam), n)
    t = fp.mul(fp.add(c0, lam), _INV2)
    u_ = fp.pow_const(t, _POW_U)
    pr = fp.mul(
        jnp.stack([u_, c1], 0),
        jnp.stack([t, jnp.broadcast_to(_INV2, t.shape)], 0),
    )
    e0, c1h = pr[0], pr[1]  # e₀ = u*·t, c1h = c1/2
    chi_one = fp.eq(fp.mul(u_, e0), fp.one_mont(e0.shape[:-1]))
    f0 = fp.mul(c1h, u_)
    e = fp.select(chi_one, e0, fp.neg(f0))
    f = fp.select(chi_one, f0, e0)
    y = jnp.stack([e, f], axis=-2)
    ok = lam_ok & fp2.eq(fp2.square(y), c)
    return y, ok


def _y_is_lex_larger(y):
    """ZCash sort flag: y > −y comparing (c1, then c0) canonically."""
    yc = jnp.stack([fp.from_mont(y[..., 0, :]), fp.from_mont(y[..., 1, :])], -2)
    c0_big = fp._lex_ge(yc[..., 0, :], _HALF_P1)
    c1_big = fp._lex_ge(yc[..., 1, :], _HALF_P1)
    c1_zero = jnp.all(yc[..., 1, :] == 0, axis=-1)
    return jnp.where(c1_zero, c0_big, c1_big)


def decompress(raw):
    """Decompress ZCash-format G2 signatures on device.

    raw: (..., 96) uint8. Returns (x, y, ok):
    x, y (..., 2, 32) Montgomery limbs of an affine curve point; ok bool —
    False for malformed flags, out-of-range coordinates, off-curve x, the
    infinity encoding (an infinity signature never verifies per eth2), or
    the sqrt corner documented above. Coordinates of !ok lanes are
    garbage; callers must mask. Subgroup membership is NOT checked here —
    the verifier checks its random plane sums instead
    (`planes_in_subgroup`)."""
    raw = jnp.asarray(raw)
    flags = raw[..., 0].astype(jnp.int32)
    compressed = (flags & 0x80) != 0
    infinity = (flags & 0x40) != 0
    sign = (flags & 0x20) != 0

    top = raw.astype(jnp.int32).at[..., 0].set(flags & 0x1F)
    xc1 = _bytes48_to_limbs(top[..., :48])
    xc0 = _bytes48_to_limbs(top[..., 48:96])
    in_range = _lex_lt_p(xc1) & _lex_lt_p(xc0)
    x = jnp.stack([fp.to_mont(xc0), fp.to_mont(xc1)], axis=-2)

    # y² = x³ + 4(1+u)
    xsq = fp2.square(x)
    b2 = jnp.asarray(
        np.stack([fq_to_limbs(Fq(4)), fq_to_limbs(Fq(4))])
    )
    rhs = fp2.add(fp2.mul(xsq, x), b2)
    y, sqrt_ok = fp2_sqrt(rhs)
    flip = _y_is_lex_larger(y) != sign
    y = fp2.select(~flip, y, fp2.neg(y))

    ok = compressed & ~infinity & in_range & sqrt_ok
    return x, y, ok


def g2_mul_x_abs(p):
    """[|x|]·P for the BLS parameter |x|: 63 doublings and 5 additions as
    ONE scan over the constant bits of |x|, the addition behind a
    scalar-predicate `lax.cond` — the point operations are traced and
    compiled once instead of 68 times (a TPU compile pays per site)."""

    def step(acc, bit):
        acc = g2.double(acc)
        acc = lax.cond(bit != 0, lambda a: g2.add(a, p), lambda a: a, acc)
        return acc, None

    acc, _ = lax.scan(step, p, jnp.asarray(_X_ABS_BITS_TAIL))
    return acc


def planes_in_subgroup(u_planes):
    """ψ(U_b) == [x]·U_b over the leading plane axis → scalar bool.

    x = X_PARAM < 0, so the right side is −[|x|]·U_b. Infinity planes
    pass (ψ(O) = O = [x]O) via the projective eq's infinity case —
    correct: an all-zero mask says nothing and contributes nothing."""
    lhs = g2_psi(u_planes)
    rhs = g2.neg(g2_mul_x_abs(u_planes))
    return jnp.all(g2.eq(lhs, rhs))
