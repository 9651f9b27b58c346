"""Optimal ate pairing on BLS12-381 — device tier (jit/vmap-able JAX).

Re-implements the oracle (`bls/pairing.py`) the TPU way:
- Q stays on the twist E'(Fp2) in homogeneous projective coordinates; lines
  are evaluated through the untwist (x/w², y/w³) and scaled by w³ and by
  Fp2 denominators — both annihilated by the final exponentiation (w^N = 1
  since 6(p²−1) | N = (p¹²−1)/r), so no inversions inside the loop.
- The Miller loop is ONE `lax.scan` over the 63 parameter bits; the rare
  addition step (6 set bits in |x|) sits behind `lax.cond` with a scalar
  (unbatched) predicate, so XLA keeps it a real branch and zero-bit
  iterations skip the addition entirely — batched pairings share the branch
  because the bit pattern is the same for every lane.
- Final exponentiation: easy part (p⁶−1)(p²+1) then the HHT hard part,
  matching the oracle's convention (computes pairing³ — harmless for
  verification equations; see bls/pairing.py:104 docstring).

Conventions (MUST match the oracle bit-for-bit — differential tests):
miller_loop returns conj(f_{|x|,Q}(P)); e(O, Q) = e(P, O) = 1 handled by
the caller via masks (`pairing_check` below).

Reference analog: the blst pairing core behind verifyMultipleSignatures
(`chain/bls/maybeBatch.ts:18-27` per SURVEY.md §2.3) — here it is a
vmap'd kernel over signature sets instead of a worker-thread C call.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..bls.fields import X_PARAM
from . import fp, fp2, fp12
from .points import g2

X_ABS = abs(X_PARAM)
# MSB-first bits of |x| after the leading 1 (63 scan steps, 5 ones)
_X_BITS_TAIL = np.array([int(b) for b in bin(X_ABS)[3:]], dtype=np.int32)


# one stacked fp2 multiply over a new leading axis — the latency
# discipline of ops/points.py applied to the Miller step (same helper:
# g2 is the CurveOps instance whose field is fp2)
def _stack_mul(lhs, rhs):
    return g2._mulstack(lhs, rhs)


def _lift_fp(a):
    """Fp element (..., 32) → Fp2 with zero imaginary part, so Fp scalings
    can ride the stacked fp2 multiplies."""
    import jax.numpy as jnp

    return jnp.stack([a, jnp.zeros_like(a)], axis=-2)


def _line_and_double(t, xp_neg2, yp2, zp2, b3):
    """Fused tangent line + point doubling for the Miller step.

    Line (scaled by 2YZ²·w³, ×Zp for projective P):
        l0 = 3X³ − 2Y²Z,  l1 = 3X²Z·(−xp),  l2 = 2YZ²·yp
    Double: RCB16 Algorithm 9 (a=0) on the twist.

    The two share X², Y², Z², YZ, XY — everything runs as THREE stacked
    fp2 multiplies (5+5+7 products) instead of ~9 sequential ones
    (profile: the Miller scan body is latency-bound like the ladders).
    xp_neg2/yp2/zp2 are the G1 evaluation point lifted to Fp2 (zero
    imaginary part); zp2 is None for affine P."""
    x, y, z = t
    W = fp.wrap
    # stage A: shared quadratic monomials
    xx, yy, zz, yz, xy = _stack_mul([x, y, z, y, x], [x, y, z, z, y])
    # stage B: cubics + the b3 scaling
    xxx, yyz, xxz, yzz, t2b = _stack_mul(
        [xx, yy, xx, yz, b3], [x, z, z, z, zz]
    )
    # combines grouped into TWO bounds-tracked scans by candidate count
    # (round 4: ~12 sequential add scans)
    y3s, two_yzz, three_xxz = fp.reduce_stack(
        [W(yy) + W(t2b), W(yzz).double(),
         W(xxz).double() + W(xxz)]
    )
    z8, l0, t0c = fp.reduce_stack(
        [W(yy).double().double().double(),                      # 8Y²
         W(xxx).double() + W(xxx) - W(yyz).double(),            # 3X³ − 2Y²Z
         W(yy) - (W(t2b).double() + W(t2b))]
    )
    # stage C: line evaluations + double outputs
    lhs = [three_xxz, two_yzz, t2b, yz, t0c, t0c]
    rhs = [xp_neg2, yp2, z8, z8, y3s, xy]
    if zp2 is not None:
        lhs.append(l0)
        rhs.append(zp2)
    out = _stack_mul(lhs, rhs)
    l1, l2, x3, z3, y3m, xt = out[:6]
    if zp2 is not None:
        l0 = out[6]
    ox, oy = fp.reduce_stack([W(xt).double(), W(x3) + W(y3m)])
    t_next = (ox, oy, z3)
    return l0, l1, l2, t_next


def _line_and_add_projq(t, q_proj, xp_neg2, yp2, zp2, b3):
    """Fused chord line + FULL projective addition T+Q (Q projective).

    Same line as `_line_and_add` scaled uniformly by Zq² (a subfield
    factor, annihilated by the final exponentiation): with
    θ' = Y·Zq − Yq·Z = Zq·θ and H' = X·Zq − Xq·Z = Zq·H,
        l0 = θ'·Xq − Yq·H',  l1 = (Zq·θ')·(−xp),  l2 = (Zq·H')·yp.
    Addition: RCB16 Algorithm 7 (a=0), both operands projective — the
    grouped batch equation feeds Q lanes that come out of point sums
    (projective), and one inversion per lane would dwarf the Miller loop.
    Three stacked fp2 multiplies (8+6+9), mirroring the mixed variant."""
    x, y, z = t
    xq, yq, zq = q_proj
    W = fp.wrap
    sxy, sq = fp.reduce_sums(jnp.stack([x + y, xq + yq]))
    # stage A: RCB16 cross products + the four line cross terms
    t0, t1, t2, u, yzq, yqz, xzq, xqz = _stack_mul(
        [x, y, z, sxy, y, yq, x, xq],
        [xq, yq, zq, sq, zq, z, zq, z],
    )
    theta, h, t3, t4, y3p, x3 = fp.reduce_stack(
        [W(yzq) - W(yqz),              # Zq·(Y − yq·Z)
         W(xzq) - W(xqz),              # Zq·(X − xq·Z)
         W(u) - W(t0) - W(t1),
         W(yzq) + W(yqz),
         W(xzq) + W(xqz),
         W(t0).double() + W(t0)]
    )
    # stage B: b3 scalings + line products
    t2b, th_xq, yq_h, thz, hz, y3 = _stack_mul(
        [b3, theta, yq, zq, zq, b3], [t2, xq, h, theta, h, y3p]
    )
    l0, z3, t1m = fp.reduce_stack(
        [W(th_xq) - W(yq_h), W(t1) + W(t2b), W(t1) - W(t2b)]
    )
    # stage C: addition outputs + the two line evaluations (+ optional l0·zp)
    lhs = [t3, t4, y3, t1m, z3, x3, thz, hz]
    rhs = [t1m, y3, x3, z3, t4, t3, xp_neg2, yp2]
    if zp2 is not None:
        lhs.append(l0)
        rhs.append(zp2)
    out = _stack_mul(lhs, rhs)
    a, b, c, d, e, f, l1, l2 = out[:8]
    if zp2 is not None:
        l0 = out[8]
    ox, oy, oz = fp.reduce_stack(
        [W(a) - W(b), W(c) + W(d), W(e) + W(f)]
    )
    return l0, l1, l2, (ox, oy, oz)


def _line_and_add(t, q_aff, xp_neg2, yp2, zp2, b3):
    """Fused chord line + mixed addition T+Q for the Miller step.

    Line (scaled by H·w³, ×Zp for projective P) with θ = Y − yq·Z,
    H = X − xq·Z:  l0 = θ·xq − yq·H,  l1 = θ·(−xp),  l2 = H·yp.
    Addition: RCB16 Algorithm 8 (a=0), Q affine. Three stacked fp2
    multiplies (6+6+7) instead of ~9 sequential."""
    x, y, z = t
    xq, yq = q_aff
    W = fp.wrap
    sxy, sq = fp.reduce_sums(jnp.stack([x + y, xq + yq]))
    # stage A: line + addition cross products (xq·z / yq·z shared)
    t0, t1, u, xqz, yqz, b3z = _stack_mul(
        [x, y, sxy, xq, yq, b3], [xq, yq, sq, z, z, z]
    )
    theta, h, t3, y3p, t4, x3, z3, t1m = fp.reduce_stack(
        [W(y) - W(yqz),
         W(x) - W(xqz),
         W(u) - W(t0) - W(t1),
         W(xqz) + W(x),
         W(yqz) + W(y),
         W(t0).double() + W(t0),
         W(t1) + W(b3z),
         W(t1) - W(b3z)]
    )
    # stage B: line products + the b3·y3p scaling
    th_xq, yq_h, l1, l2, y3 = _stack_mul(
        [theta, yq, theta, h, b3], [xq, h, xp_neg2, yp2, y3p]
    )
    l0 = fp2.sub(th_xq, yq_h)
    # stage C: addition outputs (+ optional l0·zp)
    lhs = [t3, t4, y3, t1m, z3, x3]
    rhs = [t1m, y3, x3, z3, t4, t3]
    if zp2 is not None:
        lhs.append(l0)
        rhs.append(zp2)
    out = _stack_mul(lhs, rhs)
    a, b, c, d, e, f = out[:6]
    if zp2 is not None:
        l0 = out[6]
    ox, oy, oz = fp.reduce_stack(
        [W(a) - W(b), W(c) + W(d), W(e) + W(f)]
    )
    return l0, l1, l2, (ox, oy, oz)


def miller_loop(p_aff, q_aff):
    """f = conj(f_{|x|,Q}(P)) for P ∈ G1 affine (xp, yp limbs), Q ∈ G2
    affine ((2,32)-limb coords). Batched over leading axes; does NOT handle
    infinity — callers mask (see `pairing_check`)."""
    return _miller_loop_impl(p_aff[0], p_aff[1], None, q_aff[0], q_aff[1], None)


def miller_loop_projective(p_proj, q_aff):
    """Same as `miller_loop` but P = (Xp, Yp, Zp) homogeneous projective —
    equal post-final-exp, up to the Zp^k subfield scale (see `_line_dbl`).
    Zp = 0 lanes produce garbage; callers mask them."""
    return _miller_loop_impl(
        p_proj[0], p_proj[1], p_proj[2], q_aff[0], q_aff[1], None
    )


def miller_loop_proj_pq(p_proj, q_proj):
    """P AND Q homogeneous projective — equal post-final-exp up to Zp/Zq
    subfield scales. The grouped batch equation's form: its Q lanes come
    out of on-device point sums (projective), and a per-lane Fp2 inversion
    (~570 sequential multiplies via Fermat) would dwarf the whole Miller
    loop. Zp = 0 or Zq = 0 lanes produce garbage; callers mask them."""
    return _miller_loop_impl(
        p_proj[0], p_proj[1], p_proj[2], q_proj[0], q_proj[1], q_proj[2]
    )


def _miller_loop_impl(xp, yp, zp, xq, yq, zq):
    batch = jnp.broadcast_shapes(xp.shape[:-1], xq.shape[:-2])
    # Unbatched inputs ride a unit batch axis: every deep Fp12 chain is
    # then traced in one batched form, so scalar and batched callers run
    # the same code (the axis costs nothing).
    if batch == ():
        out = _miller_loop_impl(
            xp[None],
            yp[None],
            None if zp is None else zp[None],
            xq[None],
            yq[None],
            None if zq is None else zq[None],
        )
        return out[0]
    xp = jnp.broadcast_to(xp, batch + xp.shape[-1:])
    yp = jnp.broadcast_to(yp, batch + yp.shape[-1:])
    if zp is not None:
        zp = jnp.broadcast_to(zp, batch + zp.shape[-1:])
    xq = jnp.broadcast_to(xq, batch + xq.shape[-2:])
    yq = jnp.broadcast_to(yq, batch + yq.shape[-2:])
    if zq is not None:
        zq = jnp.broadcast_to(zq, batch + zq.shape[-2:])
    # lift the G1 evaluation point into Fp2 once so its scalings join the
    # fused stacked multiplies of _line_and_double/_line_and_add
    xp_neg2 = _lift_fp(fp.neg(xp))
    yp2 = _lift_fp(yp)
    zp2 = None if zp is None else _lift_fp(zp)
    b3 = g2.b3

    t0 = g2.from_affine(xq, yq) if zq is None else (xq, yq, zq)
    f0 = fp12.one(batch)

    def step(carry, bit):
        t, f = carry
        l0, l1, l2, t = _line_and_double(t, xp_neg2, yp2, zp2, b3)
        f = fp12.mul_by_line(fp12.square(f), l0, l1, l2)

        def with_add(operand):
            t_in, f_in = operand
            if zq is None:
                a0, a1, a2, t_out = _line_and_add(
                    t_in, (xq, yq), xp_neg2, yp2, zp2, b3
                )
            else:
                a0, a1, a2, t_out = _line_and_add_projq(
                    t_in, (xq, yq, zq), xp_neg2, yp2, zp2, b3
                )
            f_out = fp12.mul_by_line(f_in, a0, a1, a2)
            return t_out, f_out

        t, f = lax.cond(bit != 0, with_add, lambda o: o, (t, f))
        return (t, f), None

    (t_final, f), _ = lax.scan(step, (t0, f0), jnp.asarray(_X_BITS_TAIL))
    del t_final
    return fp12.conj(f)


def _pow_x_abs(g):
    """g^|x| via square-and-multiply scan (63 squarings, 5 multiplies behind
    a scalar-predicate cond). Callers are all inside the final
    exponentiation's hard part, so g is cyclotomic and the squarings use
    the Granger–Scott form (9 Fp2 squares vs 12 Fp2 products)."""

    def step(acc, bit):
        acc = fp12.cyclotomic_square(acc)
        acc = lax.cond(bit != 0, lambda a: fp12.mul(a, g), lambda a: a, acc)
        return acc, None

    acc, _ = lax.scan(step, g, jnp.asarray(_X_BITS_TAIL))
    return acc


def _pow_x(g):
    """g^x, x negative: g^|x| then conjugate (cyclotomic inverse)."""
    return fp12.conj(_pow_x_abs(g))


def _hard_part(f):
    """HHT hard part on a cyclotomic element (computes pairing³ —
    preserves == 1 checks since 3 ∤ r):

        t = f^x·f̄,  a = t^x·t̄,  b = a^x·a^p,
        c = (b^x)^x · (b^(p²)·b̄),  result = c·f³

    The five x-powers run as ONE loop — round i raises the running value
    to x and multiplies by that round's factor (f̄, t̄, a^p, 1, b^(p²)·b̄)
    — so the x-power scan and the multiplies around it are compiled once
    instead of five times (a TPU compile pays per multiply site)."""

    def round_(i, carry):
        cur, b = carry
        b = jnp.where(i == 3, cur, b)  # round 3's input is b
        fac = jnp.where(
            i < 2, fp12.conj(cur),
            jnp.where(
                i == 2, fp12.frobenius(cur, 1),
                jnp.where(
                    i == 3, fp12.one(cur.shape[:-4]),
                    fp12.mul(fp12.frobenius(b, 2), fp12.conj(b)),
                ),
            ),
        )
        return fp12.mul(_pow_x(cur), fac), b

    c, _ = lax.fori_loop(0, 5, round_, (f, f))
    f3 = fp12.mul(fp12.mul(f, f), f)
    return fp12.mul(c, f3)


def final_exponentiation(f):
    """Easy part then HHT hard part — mirrors oracle final_exponentiation
    (computes pairing³; preserves == 1 checks since 3 ∤ r)."""
    if f.ndim == 4:
        # unit-batch wrapper: see the note in _miller_loop_impl
        return final_exponentiation(f[None])[0]
    f = fp12.mul(fp12.conj(f), fp12.inv(f))  # f^(p⁶−1)
    f = fp12.mul(fp12.frobenius(f, 2), f)  # ^(p²+1): cyclotomic now
    return _hard_part(f)


def final_exponentiation_batch(fs):
    """`final_exponentiation` over axis 0 with the easy part's Fp12
    inversion AMORTIZED: fp12.batch_inv runs ONE Fermat inversion chain
    for the whole batch (Montgomery product trick) instead of one ~570-
    sequential-multiply chain per lane. The hard part is already pure
    vmapped scan work and shares its latency across lanes for free.

    The shared final-exp entry for EVERY verdict path (ISSUE 14): the
    per-set/grouped/pk-grouped/bisect kernels and their sharded twins all
    route here (`final_exponentiation_one` for single products). Two
    contracts beyond the per-lane form:

    - zero lanes are SAFE: a zero lane would poison the whole batch
      through the Montgomery product, so zero lanes are substituted with
      the identity before `batch_inv` and their inverse forced back to
      zero afterwards — exactly what the per-lane Fermat chain computes
      for zero (0^(p−2) = 0), keeping the entry bit-identical to
      per-lane `final_exponentiation` on EVERY input (differential tests
      in tests/test_ops_pairing.py and tests/test_final_exp_batch.py).
    - the hard part's ~1,000 sequential small muls can run the scan-free
      Kogge–Stone carry (`fp.ks_carry`) via
      LODESTAR_TPU_FINAL_EXP_KS_CARRY=1; measured on the CPU backend the
      carry_scan default stays (compile/runtime numbers in
      docs/architecture.md §"Final-exp batching"),
      and the knob is confined to THIS kernel — the site count elsewhere
      blows the compile budget (fp.py round-2 lesson).
    """
    from ..utils.env import env_bool

    carry_ctx = (
        fp.carry_form(fp._ks_carry_impl)
        if env_bool("LODESTAR_TPU_FINAL_EXP_KS_CARRY")
        else fp.carry_form(None)
    )
    with carry_ctx:
        nz = ~jnp.all(fp.canonical(fs) == 0, axis=(-1, -2, -3, -4))
        safe = fp12.select(nz, fs, fp12.one(fs.shape[:-4]))
        inv = fp12.select(nz, fp12.batch_inv(safe), fp12.zero(fs.shape[:-4]))
        f = fp12.mul(fp12.conj(fs), inv)  # f^(p⁶−1)
        f = fp12.mul(fp12.frobenius(f, 2), f)  # ^(p²+1): cyclotomic now
        return _hard_part(f)


def final_exponentiation_one(f):
    """Final exponentiation of ONE product, routed through the shared
    batched kernel: a unit batch axis keeps deep fp12 chains batched (as
    in `_miller_loop_impl`) and keeps every verdict path
    on a single consensus-critical final-exp implementation. For n = 1
    `fp12.batch_inv` degenerates to `fp12.inv`, so this is bit-identical
    to per-lane `final_exponentiation`."""
    return final_exponentiation_batch(f[None])[0]


def pairing(p_aff, q_aff):
    return final_exponentiation(miller_loop(p_aff, q_aff))


def pairing_check(p_affs, q_affs, valid_mask):
    """Π_i e(P_i, Q_i) == 1 over the batch axis 0 (the multi-pairing
    verification primitive, oracle: bls/pairing.multi_pairing).

    p_affs = (xp, yp) with leading batch axis; q_affs = (xq, yq) likewise;
    valid_mask (batch,) bool — False lanes contribute 1 (the e(O, ·) = 1
    convention for infinity inputs).
    """
    if p_affs[0].shape[0] == 0:
        return jnp.asarray(True)  # empty product == 1 (vacuous truth)
    fs = miller_loop(p_affs, q_affs)
    fs = fp12.select(valid_mask, fs, fp12.one(fs.shape[:-4]))
    return fp12.is_one(final_exponentiation_one(fp12.product_tree(fs)))
