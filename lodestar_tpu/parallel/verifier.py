"""Batched BLS signature-set verification on device.

The device analog of blst's `verifyMultipleSignatures` as consumed by the
reference's `BlsMultiThreadWorkerPool` (`chain/bls/multithread/index.ts:98`,
`maybeBatch.ts:16-27` per SURVEY.md §2.2): verify N signature sets with one
random-linear-combination pairing equation

    Π_i e(r_i·pk_i, H(m_i)) · e(−g1, Σ_i r_i·sig_i) == 1

where r_i are independent nonzero 64-bit scalars. Where the reference
chunks sets across worker threads, here the whole batch is ONE XLA
dispatch: scalar muls, N+1 Miller loops, a log-depth Fp12 product and a
single shared final exponentiation, all vmapped over the batch axis.

Design notes (TPU-first):
- Fixed batch buckets (powers of two) keep shapes static — one compile per
  bucket, reused forever. Padding lanes are masked to the Fp12 identity.
- r_i·pk_i stays projective out of the scalar-mul scan; the Miller loop
  accepts projective P by scaling lines with Zp ∈ Fp (annihilated by the
  final exponentiation) — no per-lane field inversion anywhere. The only
  inversion in the kernel is ONE Fp2 inv for the aggregated signature.
- The per-set retry path of the reference (`multithread/worker.ts:55-95`:
  batch fails → verify each set alone) is `verify_individual`: one batched
  dispatch computing every per-set verdict, not N round-trips.

Host-side preprocessing (deserialization, subgroup checks, hash-to-curve)
currently runs through the CPU oracle; moving it to C++/device SSWU is the
next tier.
"""

from __future__ import annotations

import secrets
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..bls import api as bls_api
from ..bls.hash_to_curve import hash_to_g2
from ..observability.stages import default_pipeline
from ..observability.trace import named_scope
from ..testing import faults as _faults
from ..ops import fp, fp2, fp12, msm
from ..ops.g2_decompress import decompress as _g2_decompress, planes_in_subgroup as _planes_in_subgroup
from ..ops.io_host import g1_affine_to_limbs, g2_affine_to_limbs
from ..ops.pairing import (
    final_exponentiation,
    final_exponentiation_batch,
    final_exponentiation_one,
    miller_loop,
    miller_loop_proj_pq,
)
from ..ops.points import (
    G1_GEN_X,
    G1_GEN_Y,
    NEG_G1_POW2_64_X,
    NEG_G1_POW2_64_Y,
    NEG_G1_POW2_X,
    NEG_G1_POW2_Y,
    g1,
    g2,
    g2_psi,
)

N_LIMBS = 32
R_BITS = 64  # random-coefficient width (matches blst's 64-bit rand scaling)
HALF_BITS = 32  # the a/b halves of the r = a + z·b GLS split
PROBE_LANES = 16  # bisection probe batch width: ONE compiled shape, chunked

__all__ = [
    "BatchVerifier",
    "TpuBlsVerifier",
    "SetArrays",
    "GroupedArrays",
    "PkGroupedArrays",
    "grouped_verify_kernel",
    "pk_grouped_verify_kernel",
    "bisect_tree_kernel",
    "bisect_probe_kernel",
]


_fp12_product_tree = fp12.product_tree

# host-side Fp12 identity for bisection probe padding (lazy: building it
# touches the device, which import-time code must not)
_FP12_ONE_NP = None


def _g2_sum_tree(ps):
    """log2-depth complete-add reduction of G2 projective points over axis 0."""
    x, y, z = ps
    n = x.shape[0]
    while n > 1:
        half = n // 2
        a = (x[:half], y[:half], z[:half])
        b = (x[half : 2 * half], y[half : 2 * half], z[half : 2 * half])
        hx, hy, hz = g2.add(a, b)
        if n % 2 != 0:
            hx = jnp.concatenate([hx, x[2 * half :]], 0)
            hy = jnp.concatenate([hy, y[2 * half :]], 0)
            hz = jnp.concatenate([hz, z[2 * half :]], 0)
        x, y, z = hx, hy, hz
        n = x.shape[0]
    return x[0], y[0], z[0]


def batch_verify_kernel(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
    return _batch_verify_impl(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid,
        check_planes=False,
    )


def batch_verify_kernel_raw(pk_x, pk_y, msg_x, msg_y, sig_raw, r_bits, valid):
    """`batch_verify_kernel` taking RAW 96-byte compressed signatures.

    Device-side decompression + batched plane subgroup check
    (`ops/g2_decompress` — VERDICT r4 #5): the host's only signature work
    is a memcpy. Any valid lane whose signature fails decoding (bad
    flags, off-curve, infinity) makes the verdict False — matching the
    host-marshal path, where `_native_limbs` returns None and the caller
    reports False."""
    with named_scope("bls/g2_decompress"):
        sig_x, sig_y, dec_ok = _g2_decompress(sig_raw)
    decode_fail = jnp.any(valid & ~dec_ok)
    verdict = _batch_verify_impl(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits,
        valid & dec_ok, check_planes=True,
    )
    return verdict & ~decode_fail


def _batch_verify_impl(
    pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid, check_planes
):
    """All-or-nothing batch verification; shapes (N, …) static.

    pk_*  (N, 32)     G1 affine Montgomery limbs (pre-aggregated pubkeys)
    msg_* (N, 2, 32)  G2 affine limbs of H(m_i)
    sig_* (N, 2, 32)  G2 affine limbs of signatures
    r_bits (N, 64)    random coefficients, MSB-first bits
    valid (N,) bool   padding mask — False lanes are ignored
    Returns scalar bool.

    Round-4 restructure of the signature aggregate (the all-unique
    worst-case shape is adversary-selectable — VERDICT r3 #1): instead of
    per-lane 64-step G2 ladders + a sum tree + one affine inversion,
    Σ r_i·sig_i rides the grouped kernel's constant-lane trick —
    per-bit-plane masked sums U_b (subset-4 tables, `ops/msm.py`) paired
    against precomputed −[2^b]g1, so e(−g1, Σ 2^b U_b) = Π_b e(−[2^b]g1,
    U_b) with NO sequential recombination. G1 r_i·pk_i keeps its bit
    ladder (it feeds per-set Miller lanes; measured cheap). Projective-Q
    Miller costs only the 6 sparse add steps extra.

    Bit ladders, NOT the windowed variant, for the G1 side: measured on
    v5e (tools/win_check) the 2^4-window table selects cost more than the
    saved adds and XLA compile time grows ~30x.
    """
    n = pk_x.shape[0]
    # r_i·pk_i (G1, projective out of the scan — no inversion)
    with named_scope("bls/scalar_mul"):
        rpk = g1.scalar_mul_bits(r_bits, (pk_x, pk_y))

    # signature side: global bit-plane sums over all N lanes (LSB-first
    # planes; r_bits arrive MSB-first)
    sig = (sig_x, sig_y, fp2.one((n,)))
    sig = g2.select(valid, sig, g2.infinity((n,)))
    with named_scope("bls/msm_planes"):
        u_planes = msm.masked_plane_sums(
            g2, sig, jnp.flip(r_bits, axis=-1)
        )  # (64, …) projective

    # Pair lanes: N (r_i·pk_i, H(m_i)) plus 64 (−[2^b]g1, U_b)
    px = jnp.concatenate([rpk[0], NEG_G1_POW2_64_X], 0)
    py = jnp.concatenate([rpk[1], NEG_G1_POW2_64_Y], 0)
    pz = jnp.concatenate([rpk[2], fp.one((R_BITS,))], 0)
    qx = jnp.concatenate([msg_x, u_planes[0]], 0)
    qy = jnp.concatenate([msg_y, u_planes[1]], 0)
    qz = jnp.concatenate([fp2.one((n,)), u_planes[2]], 0)
    lane_ok = jnp.concatenate(
        [valid, ~g2.is_infinity(u_planes)], 0
    )

    with named_scope("bls/miller_loop"):
        fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    fs = fp12.select(lane_ok, fs, fp12.one((n + R_BITS,)))
    with named_scope("bls/product_tree"):
        prod = _fp12_product_tree(fs)
    with named_scope("bls/final_exp_batch"):
        verdict = fp12.is_one(final_exponentiation_one(prod))
    if check_planes:
        # signature subgroup membership, batched: ψ(U_b) == [x]U_b on the
        # 64 random bit-planes (2^-63 even with the forced-nonzero bit —
        # soundness analysis in ops/g2_decompress.py)
        verdict = verdict & _planes_in_subgroup(u_planes)
    return verdict


def grouped_verify_kernel(
    pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid
):
    return _grouped_verify_impl(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid,
        check_planes=False,
    )


def grouped_verify_kernel_raw(
    pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid
):
    """`grouped_verify_kernel` taking RAW 96-byte compressed signatures
    (R, L, 96) — device decompression + plane subgroup checks, same
    contract as `batch_verify_kernel_raw`."""
    with named_scope("bls/g2_decompress"):
        sig_x, sig_y, dec_ok = _g2_decompress(sig_raw)
    decode_fail = jnp.any(valid & ~dec_ok)
    verdict = _grouped_verify_impl(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits,
        valid & dec_ok, check_planes=True,
    )
    return verdict & ~decode_fail


def _grouped_verify_impl(
    pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid, check_planes
):
    """Batch verification GROUPED by signing root — the gossip-shape fast
    path (round-3 perf centerpiece; VERDICT r2 Missing #1).

    Real gossip traffic shares signing roots (every member of a committee
    signs the same data — the reference pre-aggregates pubkeys per SET for
    this reason, `chain/bls/utils.ts:5-16`; here the whole BATCH equation
    is regrouped by bilinearity):

        Π_j e(Σ_{i∈j} r_i·pk_i, H_j) · e(−g1, Σ_i r_i·sig_i) == 1

    R root-rows × L lanes replace N+1 Miller loops with 2R+64 — at the
    64-root gossip shape that is ~60× fewer pairings. Three structural
    moves keep everything off the sequential-latency floor:

    - GLS split randomness: r_i = a_i + z·b_i with a_i, b_i uniform
      32-bit ((a,b) ↦ a+z·b is injective mod r, so r_i is uniform over
      2^64 residues — soundness unchanged at 2^-64) and ψ(Q) = [z]Q
      two fp2 multiplies. Halves every bit-plane depth.
    - per-root pubkey sums P_j = A_j + [z]B_j via bit-plane MSM
      (`ops/msm.py`): subset-4 tables + per-plane tree sums, then ONE
      Horner over 32 planes vectorized across (2, R) lanes; the [z]
      lands as e(B_j, ψ(H_j)) — no device scalar ladders at all.
    - the signature aggregate never gets Horner-combined: each plane
      U_b = Σ bit_b(a_i)·sig_i pairs against the CONSTANT −[2^b]g1
      (e(−g1, Σ 2^b U_b) = Π_b e(−[2^b]g1, U_b)), and the b-half rides
      the same constants through ψ(U'_b).

    Shapes (static): pk_* (R, L, 32); msg_* (R, 2, 32) — ONE H(m) per
    root-row; sig_* (R, L, 2, 32); a_bits/b_bits (R, L, 32) LSB-first;
    valid (R, L). L % 4 == 0. Rows may repeat a root (the marshaller
    splits >L-set roots across rows — bilinearity doesn't care). Padding
    lanes/rows are masked to infinity and contribute 1. Returns scalar
    bool, all-or-nothing like `batch_verify_kernel`.
    """
    R, L = pk_x.shape[0], pk_x.shape[1]
    n = R * L
    # mask invalid lanes to infinity (complete formulas absorb them)
    pk = (pk_x, pk_y, fp.one((R, L)))
    pk = g1.select(valid, pk, g1.infinity((R, L)))
    bits = jnp.concatenate([a_bits, b_bits], axis=-1)  # (R, L, 64)

    # per-root bit-plane sums: (64, R) G1 projective
    with named_scope("bls/msm_planes"):
        t_planes = msm.masked_plane_sums(g1, pk, bits)
        # A_j (a-half) and B_j (b-half) via one Horner over (2, R) lanes
        tp = tuple(c.reshape((2, HALF_BITS) + c.shape[1:]) for c in t_planes)
        tp = tuple(jnp.moveaxis(c, 1, 0) for c in tp)  # (32, 2, R, …)
        ab = msm.horner_pow2(g1, tp)  # (2, R) projective
    a_pt = tuple(c[0] for c in ab)
    b_pt = tuple(c[1] for c in ab)

    # signature side: global bit-plane sums over all N lanes
    sig = (
        sig_x.reshape((n,) + sig_x.shape[-2:]),
        sig_y.reshape((n,) + sig_y.shape[-2:]),
        fp2.one((n,)),
    )
    sig = g2.select(valid.reshape(n), sig, g2.infinity((n,)))
    with named_scope("bls/msm_planes"):
        u_planes = msm.masked_plane_sums(
            g2, sig, bits.reshape(n, 2 * HALF_BITS)
        )
    u_a = tuple(c[:HALF_BITS] for c in u_planes)
    u_b = g2_psi(tuple(c[HALF_BITS:] for c in u_planes))

    # Miller lanes: (A_j, H_j), (B_j, ψH_j), (−[2^b]g1, U_b), (−[2^b]g1, ψU'_b)
    h = (msg_x, msg_y, fp2.one((R,)))
    psi_h = g2_psi(h)
    px = jnp.concatenate(
        [a_pt[0], b_pt[0], NEG_G1_POW2_X, NEG_G1_POW2_X], 0
    )
    py = jnp.concatenate(
        [a_pt[1], b_pt[1], NEG_G1_POW2_Y, NEG_G1_POW2_Y], 0
    )
    pz = jnp.concatenate(
        [a_pt[2], b_pt[2], fp.one((2 * HALF_BITS,))], 0
    )
    qx = jnp.concatenate([h[0], psi_h[0], u_a[0], u_b[0]], 0)
    qy = jnp.concatenate([h[1], psi_h[1], u_a[1], u_b[1]], 0)
    qz = jnp.concatenate([h[2], psi_h[2], u_a[2], u_b[2]], 0)

    # e(O, ·) = e(·, O) = 1: mask infinity lanes (empty rows, zero planes)
    lane_ok = ~g1.is_infinity((px, py, pz)) & ~g2.is_infinity((qx, qy, qz))
    with named_scope("bls/miller_loop"):
        fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    fs = fp12.select(lane_ok, fs, fp12.one((2 * R + 2 * HALF_BITS,)))
    with named_scope("bls/product_tree"):
        prod = fp12.product_tree(fs)
    with named_scope("bls/final_exp_batch"):
        verdict = fp12.is_one(final_exponentiation_one(prod))
    if check_planes:
        # u_planes BEFORE the ψ split: 64 iid random-bit planes of the
        # signature lanes (soundness analysis in ops/g2_decompress.py)
        verdict = verdict & _planes_in_subgroup(u_planes)
    return verdict


def pk_grouped_verify_kernel(
    pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid
):
    return _pk_grouped_verify_impl(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid,
        check_planes=False,
    )


def pk_grouped_verify_kernel_raw(
    pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid
):
    """`pk_grouped_verify_kernel` taking RAW 96-byte compressed signatures
    (R, L, 96) — device decompression + plane subgroup checks."""
    with named_scope("bls/g2_decompress"):
        sig_x, sig_y, dec_ok = _g2_decompress(sig_raw)
    decode_fail = jnp.any(valid & ~dec_ok)
    verdict = _pk_grouped_verify_impl(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits,
        valid & dec_ok, check_planes=True,
    )
    return verdict & ~decode_fail


def _pk_grouped_verify_impl(
    pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid, check_planes
):
    """Batch verification GROUPED BY PUBKEY — the DUAL of the root-grouped
    kernel, and the adversarial-floor defense (VERDICT r4 #2).

    An attacker can mint arbitrarily many unique `AttestationData` roots
    (defeating root-grouping), but every set still needs a VALID signature
    — and the attacker only controls boundedly many validator keys. Sets
    sharing a pubkey collapse by bilinearity on the OTHER side:

        Π_k e(pk_k, Σ_{i∈k} r_i·H_i) · e(−g1, Σ_i r_i·sig_i) == 1

    R pubkey-rows × L lanes run R+64 Miller loops instead of N+64. The
    per-row message combination Σ r_i·H_i is a G2 bit-plane MSM (same
    `ops/msm.py` machinery as the root-grouped pubkey sums, on the twist):
    GLS-split randomness halves plane depth, ψ lands the b-half, and ONE
    32-step Horner over (2, R) lanes recombines — the per-row result is a
    single G2 point added to ψ(b-half), so each row is ONE pairing lane.
    The signature aggregate rides the same constant-lane planes as every
    other kernel. The residual true worst case — distinct pubkeys AND
    distinct roots simultaneously — remains on the per-set kernel and is
    reported honestly as its own bench row.

    Shapes (static): pk_* (R, 32) — ONE pubkey per row; msg_* and sig_*
    (R, L, 2, 32); a_bits/b_bits (R, L, 32) LSB-first; valid (R, L).
    L % 4 == 0. Rows may repeat a pubkey (the planner splits >L-set
    groups across rows). Returns scalar bool, all-or-nothing.

    Reference analog: blst aggregates PUBKEYS per set for one shared
    message (`chain/bls/utils.ts:5-16`); this is the transpose — messages
    aggregated per pubkey — enabled by device-scale MSM.
    """
    R, L = msg_x.shape[0], msg_x.shape[1]
    n = R * L
    msgs = (msg_x, msg_y, fp2.one((R, L)))
    msgs = g2.select(valid, msgs, g2.infinity((R, L)))
    bits = jnp.concatenate([a_bits, b_bits], axis=-1)  # (R, L, 64)

    # per-row message bit-plane sums: (64, R) G2 projective
    with named_scope("bls/msm_planes"):
        m_planes = msm.masked_plane_sums(g2, msgs, bits)
        tp = tuple(c.reshape((2, HALF_BITS) + c.shape[1:]) for c in m_planes)
        tp = tuple(jnp.moveaxis(c, 1, 0) for c in tp)  # (32, 2, R, …)
        ab = msm.horner_pow2(g2, tp)  # (2, R) projective
    a_pt = tuple(c[0] for c in ab)
    b_pt = tuple(c[1] for c in ab)
    q_row = g2.add(a_pt, g2_psi(b_pt))  # Σ r_i·H_i per row

    # signature side: identical constant-lane planes as the other kernels
    sig = (
        sig_x.reshape((n,) + sig_x.shape[-2:]),
        sig_y.reshape((n,) + sig_y.shape[-2:]),
        fp2.one((n,)),
    )
    sig = g2.select(valid.reshape(n), sig, g2.infinity((n,)))
    with named_scope("bls/msm_planes"):
        u_planes = msm.masked_plane_sums(
            g2, sig, bits.reshape(n, 2 * HALF_BITS)
        )
    u_a = tuple(c[:HALF_BITS] for c in u_planes)
    u_b = g2_psi(tuple(c[HALF_BITS:] for c in u_planes))

    px = jnp.concatenate([pk_x, NEG_G1_POW2_X, NEG_G1_POW2_X], 0)
    py = jnp.concatenate([pk_y, NEG_G1_POW2_Y, NEG_G1_POW2_Y], 0)
    pz = jnp.concatenate([fp.one((R,)), fp.one((2 * HALF_BITS,))], 0)
    qx = jnp.concatenate([q_row[0], u_a[0], u_b[0]], 0)
    qy = jnp.concatenate([q_row[1], u_a[1], u_b[1]], 0)
    qz = jnp.concatenate([q_row[2], u_a[2], u_b[2]], 0)

    lane_ok = ~g1.is_infinity((px, py, pz)) & ~g2.is_infinity((qx, qy, qz))
    with named_scope("bls/miller_loop"):
        fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    fs = fp12.select(lane_ok, fs, fp12.one((R + 2 * HALF_BITS,)))
    with named_scope("bls/product_tree"):
        prod = fp12.product_tree(fs)
    with named_scope("bls/final_exp_batch"):
        verdict = fp12.is_one(final_exponentiation_one(prod))
    if check_planes:
        verdict = verdict & _planes_in_subgroup(u_planes)
    return verdict


def _individual_pairing_terms(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y):
    """(N,) per-set pairing products e(pk_i, H(m_i))·e(−g1, sig_i) — the
    shared front half of both per-set verdict tails below."""
    n = pk_x.shape[0]
    neg_gy = fp.neg(G1_GEN_Y)
    xs = jnp.concatenate([pk_x, jnp.broadcast_to(G1_GEN_X, (n, N_LIMBS))], 0)
    ys = jnp.concatenate([pk_y, jnp.broadcast_to(neg_gy, (n, N_LIMBS))], 0)
    qx = jnp.concatenate([msg_x, sig_x], 0)
    qy = jnp.concatenate([msg_y, sig_y], 0)
    with named_scope("bls/miller_loop"):
        fs = miller_loop((xs, ys), (qx, qy))
    return fp12.mul(fs[:n], fs[n:])


def individual_verify_kernel(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, valid):
    """Per-set verdicts in one dispatch: e(pk_i, H(m_i))·e(−g1, sig_i) == 1.

    The device replacement for the reference's retry-individually fallback
    (`multithread/worker.ts:55-95`) — instead of N sequential re-verifies,
    2N Miller loops and N final exponentiations run batched. Returns
    (N,) bool; padding lanes report False.
    """
    prod = _individual_pairing_terms(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y)
    # the (N,)-wide batched final exp is the per-set path's latency win:
    # ONE shared easy-part inversion chain instead of N (ISSUE 14)
    with named_scope("bls/final_exp_batch"):
        return fp12.is_one(final_exponentiation_batch(prod)) & valid


def individual_verify_kernel_legacy_fe(
    pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, valid
):
    """The pre-batching per-set verdict tail: N independent per-lane
    final exponentiations (one Fermat inversion chain EACH). Kept only
    as the bench `floor_fused_pairing` comparison baseline — never
    dispatched in production; must stay verdict-identical to
    `individual_verify_kernel`."""
    prod = _individual_pairing_terms(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y)
    with named_scope("bls/final_exp"):
        return fp12.is_one(final_exponentiation(prod)) & valid


def bisect_tree_kernel(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
    """Per-set randomized Fp12 terms + EVERY product-tree level, one
    final exponentiation — the bisection-verdict fast path.

    The per-set verdict path used to pay N final exps per batch
    (`individual_verify_kernel`). The classic batch-verification-with-
    bisection result does better: each lane contributes an independent
    randomized term

        f_i = ML(r_i·pk_i, H_i) · ML(−g1, r_i·sig_i)

    whose final exp is ε_i^{r_i} (ε_i = the set's pairing error). The
    product tree over f_i is materialized LEVEL BY LEVEL: the root passes
    exactly when every set is valid (up to the 2^-64 random-combination
    soundness — blst's own bound), which costs ONE final exp for the
    common all-valid case. On failure the host binary-searches the
    already-materialized internal nodes (`TpuBlsVerifier._bisect`): k
    invalid sets cost O(k·log N) probe final exps instead of N, and each
    leaf probe is EXACT (r_i < 2^64 < r is invertible mod r, so
    ε_i^{r_i} = 1 ⟺ ε_i = 1) — leaf verdicts match
    `individual_verify_kernel` bit-for-bit.

    Returns (root_ok, levels): levels[0] (M,) leaf terms with M = N
    padded to a power of two (identity padding), levels[j] (M >> j,)
    partial products, levels[-1] (1,) the root. Padding lanes (valid
    False) contribute the identity and must be reported False by the
    caller."""
    n = pk_x.shape[0]
    with named_scope("bls/scalar_mul"):
        rpk = g1.scalar_mul_bits(r_bits, (pk_x, pk_y))
        rsig = g2.scalar_mul_bits(r_bits, (sig_x, sig_y))
    neg_gy = fp.neg(G1_GEN_Y)
    px = jnp.concatenate([rpk[0], jnp.broadcast_to(G1_GEN_X, (n, N_LIMBS))], 0)
    py = jnp.concatenate([rpk[1], jnp.broadcast_to(neg_gy, (n, N_LIMBS))], 0)
    pz = jnp.concatenate([rpk[2], fp.one((n,))], 0)
    qx = jnp.concatenate([msg_x, rsig[0]], 0)
    qy = jnp.concatenate([msg_y, rsig[1]], 0)
    qz = jnp.concatenate([fp2.one((n,)), rsig[2]], 0)
    with named_scope("bls/miller_loop"):
        fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    f = fp12.mul(fs[:n], fs[n:])
    f = fp12.select(valid, f, fp12.one((n,)))
    with named_scope("bls/product_tree"):
        levels = fp12.product_levels(f)
    with named_scope("bls/final_exp_batch"):
        root_ok = fp12.is_one(final_exponentiation_one(levels[-1][0]))
    return root_ok, levels


def bisect_probe_kernel(fs):
    """(PROBE_LANES,) stacked product-tree nodes → (PROBE_LANES,) bool:
    is_one(final_exp) per lane, the easy part's inversion shared across
    the whole probe batch (`final_exponentiation_batch` — Montgomery
    product trick). Identity-padded lanes pass trivially and are sliced
    off by the host."""
    with named_scope("bls/bisect"):
        return fp12.is_one(final_exponentiation_batch(fs))


def final_exp_batch_kernel(fs):
    """(N,) stacked Fp12 products → (N,) bool via ONE shared-inversion
    batched final exp. The standalone compile unit for the warmup ladder
    and the bench floor comparison — the fused verdict kernels inline
    the same `final_exponentiation_batch` code."""
    with named_scope("bls/final_exp_batch"):
        return fp12.is_one(final_exponentiation_batch(fs))


class SetArrays:
    """Host-marshalled signature sets, padded to a fixed lane count."""

    __slots__ = ("pk_x", "pk_y", "msg_x", "msg_y", "sig_x", "sig_y", "valid", "n")

    def __init__(self, lanes: int):
        self.pk_x = np.zeros((lanes, N_LIMBS), np.int32)
        self.pk_y = np.zeros((lanes, N_LIMBS), np.int32)
        self.msg_x = np.zeros((lanes, 2, N_LIMBS), np.int32)
        self.msg_y = np.zeros((lanes, 2, N_LIMBS), np.int32)
        self.sig_x = np.zeros((lanes, 2, N_LIMBS), np.int32)
        self.sig_y = np.zeros((lanes, 2, N_LIMBS), np.int32)
        self.valid = np.zeros((lanes,), bool)
        self.n = 0


# --- host marshalling pool ---------------------------------------------------
#
# The C marshal tier releases the GIL, so a thread pool sized to the host's
# cores lifts wire→device throughput linearly (reference sizes its BLS
# worker pool identically: chain/bls/multithread/poolSize.ts:1-16 —
# "blst runs on the main thread; size workers to cores").

_MARSHAL_CHUNK = 256  # sets per pool task (~0.3 s of C work per chunk)
_POOL = None
_POOL_SIZE = 0


def marshal_pool_size() -> int:
    import os

    from ..utils.env import env_int

    override = env_int("LODESTAR_TPU_MARSHAL_THREADS")
    if override is not None:
        return max(0, override)
    return os.cpu_count() or 1


def _marshal_pool():
    """Shared ThreadPoolExecutor, or None on single-core hosts (chunking
    through a pool of one just adds overhead)."""
    global _POOL, _POOL_SIZE
    size = marshal_pool_size()
    if size <= 1:
        return None
    if _POOL is None or _POOL_SIZE != size:
        from concurrent.futures import ThreadPoolExecutor

        _POOL = ThreadPoolExecutor(max_workers=size, thread_name_prefix="bls-marshal")
        _POOL_SIZE = size
    return _POOL


class GroupedArrays:
    """Signature sets grouped by signing root into (R rows × L lanes)."""

    __slots__ = ("pk_x", "pk_y", "msg_x", "msg_y", "sig_x", "sig_y", "valid", "n")

    def __init__(self, rows: int, lanes: int):
        self.pk_x = np.zeros((rows, lanes, N_LIMBS), np.int32)
        self.pk_y = np.zeros((rows, lanes, N_LIMBS), np.int32)
        self.msg_x = np.zeros((rows, 2, N_LIMBS), np.int32)
        self.msg_y = np.zeros((rows, 2, N_LIMBS), np.int32)
        self.sig_x = np.zeros((rows, lanes, 2, N_LIMBS), np.int32)
        self.sig_y = np.zeros((rows, lanes, 2, N_LIMBS), np.int32)
        self.valid = np.zeros((rows, lanes), bool)
        self.n = 0


class PkGroupedArrays:
    """Signature sets grouped by PUBKEY into (R rows × L lanes) — one
    pubkey per row, per-lane messages/signatures (the dual layout)."""

    __slots__ = ("pk_x", "pk_y", "msg_x", "msg_y", "sig_x", "sig_y", "valid", "n")

    def __init__(self, rows: int, lanes: int):
        self.pk_x = np.zeros((rows, N_LIMBS), np.int32)
        self.pk_y = np.zeros((rows, N_LIMBS), np.int32)
        self.msg_x = np.zeros((rows, lanes, 2, N_LIMBS), np.int32)
        self.msg_y = np.zeros((rows, lanes, 2, N_LIMBS), np.int32)
        self.sig_x = np.zeros((rows, lanes, 2, N_LIMBS), np.int32)
        self.sig_y = np.zeros((rows, lanes, 2, N_LIMBS), np.int32)
        self.valid = np.zeros((rows, lanes), bool)
        self.n = 0


def _rand_bits(lanes: int, rng) -> np.ndarray:
    """(lanes, 64) nonzero random scalar bits, MSB first."""
    out = np.zeros((lanes, R_BITS), np.int32)
    for i in range(lanes):
        r = 0
        while r == 0:
            r = rng() & ((1 << R_BITS) - 1)
        out[i] = [(r >> (R_BITS - 1 - j)) & 1 for j in range(R_BITS)]
    return out


def _rand_pairs(shape: tuple[int, ...], rng=None):
    """LSB-first bit planes of the GLS-split coefficients r = a + z·b.

    Returns (a_bits, b_bits), each shape + (32,) int32 in {0,1}. (a, b)
    uniform 32-bit with (0, 0) excluded — injective into 2^64 residues, so
    the batch equation keeps blst's 2^-64 soundness. `rng` (tests only)
    supplies 64-bit words split as (low, high) = (a, b)."""
    count = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if rng is None:
        g = np.random.default_rng(secrets.randbits(128))
        a = g.integers(0, 1 << HALF_BITS, size=count, dtype=np.uint64)
        b = g.integers(0, 1 << HALF_BITS, size=count, dtype=np.uint64)
        a[(a == 0) & (b == 0)] = 1
    else:
        vals = [rng() for _ in range(count)]
        a = np.array([v & 0xFFFFFFFF for v in vals], np.uint64)
        b = np.array([v >> HALF_BITS for v in vals], np.uint64)
        a[(a == 0) & (b == 0)] = 1
    shifts = np.arange(HALF_BITS, dtype=np.uint64)[None, :]
    a_bits = ((a[:, None] >> shifts) & 1).astype(np.int32).reshape(shape + (HALF_BITS,))
    b_bits = ((b[:, None] >> shifts) & 1).astype(np.int32).reshape(shape + (HALF_BITS,))
    return a_bits, b_bits


# XLA:TPU compiles of the whole verify kernels recurse deeply: a thread's
# default 8 MiB stack has overflowed (SIGSEGV) compiling them, so the
# threads that compile concurrently get a larger one
COMPILE_THREAD_STACK = 512 << 20


def run_concurrently(calls, workers: int | None = None) -> list:
    """Run zero-arg callables on `workers` threads (default: one each),
    each thread with a COMPILE_THREAD_STACK stack; results in order,
    the first exception re-raised. For compiles, which release the GIL."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    if not calls:
        return []
    prev = threading.stack_size(COMPILE_THREAD_STACK)
    try:
        with ThreadPoolExecutor(workers or len(calls)) as ex:
            futs = [ex.submit(c) for c in calls]
    finally:
        threading.stack_size(prev)
    return [f.result() for f in futs]


class BatchVerifier:
    """Shape-bucketed jitted kernels. One compile per bucket size, cached.

    `grouped_configs` are (rows, lanes_per_row) shapes for the root-grouped
    kernel — one compile each, so the list stays short. lanes_per_row must
    be a multiple of 4 (the MSM subset-4 tables)."""

    def __init__(
        self,
        buckets: tuple[int, ...] = (4, 16, 64, 128),
        grouped_configs: tuple[tuple[int, int], ...] = ((16, 8), (64, 64)),
        pk_grouped_configs: tuple[tuple[int, int], ...] = ((128, 32),),
    ):
        self.buckets = tuple(sorted(buckets))
        self.grouped_configs = tuple(
            sorted(grouped_configs, key=lambda c: c[0] * c[1])
        )
        self.pk_grouped_configs = tuple(
            sorted(pk_grouped_configs, key=lambda c: c[0] * c[1])
        )
        for _, lanes in self.grouped_configs + self.pk_grouped_configs:
            if lanes % 4 != 0:
                raise ValueError("grouped lanes_per_row must be a multiple of 4")
        for b in self.buckets:
            if b % 4 != 0:
                # the per-set kernel's bit-plane signature sums use
                # subset-4 tables (ops/msm.py): lane counts must divide
                raise ValueError("buckets must be multiples of 4")
        # every jitted kernel goes through the compile ledger's wrap seam:
        # the first dispatch per shape signature is timed and recorded as
        # a compile event (kernel name, shape key, duration, persistent-
        # cache hit/miss) — zero overhead after the first call
        from ..observability.compile_ledger import ledger as _compile_ledger

        _wrap = _compile_ledger().wrap
        self._batch = _wrap(jax.jit(batch_verify_kernel), "batch")
        self._individual = _wrap(jax.jit(individual_verify_kernel), "individual")
        self._grouped = _wrap(jax.jit(grouped_verify_kernel), "grouped")
        self._batch_raw = _wrap(jax.jit(batch_verify_kernel_raw), "batch_raw")
        self._grouped_raw = _wrap(
            jax.jit(grouped_verify_kernel_raw), "grouped_raw"
        )
        self._pk_grouped = _wrap(jax.jit(pk_grouped_verify_kernel), "pk_grouped")
        self._pk_grouped_raw = _wrap(
            jax.jit(pk_grouped_verify_kernel_raw), "pk_grouped_raw"
        )
        self._bisect_tree = _wrap(jax.jit(bisect_tree_kernel), "bisect_tree")
        self._bisect_probe = _wrap(jax.jit(bisect_probe_kernel), "bisect_probe")
        # ISSUE 14 compile unit: the standalone shared-inversion batched
        # final exp — wrapped so its first dispatch is timed,
        # cache-classified and visible at /debug/compiles like the 9
        # fused kernels above
        self._final_exp_batch = _wrap(
            jax.jit(final_exp_batch_kernel), "final_exp_batch"
        )

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def verify_batch(self, arrs: SetArrays, r_bits: np.ndarray):
        return self._batch(
            arrs.pk_x, arrs.pk_y, arrs.msg_x, arrs.msg_y,
            arrs.sig_x, arrs.sig_y, r_bits, arrs.valid,
        )

    def verify_grouped(self, g: GroupedArrays, a_bits, b_bits):
        return self._grouped(
            g.pk_x, g.pk_y, g.msg_x, g.msg_y, g.sig_x, g.sig_y,
            a_bits, b_bits, g.valid,
        )

    def verify_batch_raw(self, arrs: SetArrays, sig_raw, r_bits):
        """Per-set kernel with on-device signature decompression;
        `sig_raw` (N, 96) uint8, `arrs.sig_*` ignored."""
        return self._batch_raw(
            arrs.pk_x, arrs.pk_y, arrs.msg_x, arrs.msg_y,
            sig_raw, r_bits, arrs.valid,
        )

    def verify_grouped_raw(self, g: GroupedArrays, sig_raw, a_bits, b_bits):
        """Grouped kernel with on-device signature decompression;
        `sig_raw` (R, L, 96) uint8, `g.sig_*` ignored."""
        return self._grouped_raw(
            g.pk_x, g.pk_y, g.msg_x, g.msg_y, sig_raw,
            a_bits, b_bits, g.valid,
        )

    def verify_pk_grouped(self, g: "PkGroupedArrays", a_bits, b_bits):
        return self._pk_grouped(
            g.pk_x, g.pk_y, g.msg_x, g.msg_y, g.sig_x, g.sig_y,
            a_bits, b_bits, g.valid,
        )

    def verify_pk_grouped_raw(self, g: "PkGroupedArrays", sig_raw, a_bits, b_bits):
        return self._pk_grouped_raw(
            g.pk_x, g.pk_y, g.msg_x, g.msg_y, sig_raw,
            a_bits, b_bits, g.valid,
        )

    def verify_individual(self, arrs: SetArrays):
        return self._individual(
            arrs.pk_x, arrs.pk_y, arrs.msg_x, arrs.msg_y,
            arrs.sig_x, arrs.sig_y, arrs.valid,
        )

    def verify_bisect_tree(self, arrs: SetArrays, r_bits: np.ndarray):
        """(root_ok, product-tree levels) for the bisection-verdict path;
        the all-valid common case is decided by root_ok alone (ONE final
        exp), levels feed `TpuBlsVerifier._bisect` on failure."""
        return self._bisect_tree(
            arrs.pk_x, arrs.pk_y, arrs.msg_x, arrs.msg_y,
            arrs.sig_x, arrs.sig_y, r_bits, arrs.valid,
        )

    def probe_nodes(self, fs: np.ndarray):
        """(PROBE_LANES,) stacked Fp12 tree nodes → (PROBE_LANES,) bool
        via one batched shared-easy-part final exp."""
        return self._bisect_probe(fs)

    def final_exp_batch(self, fs):
        """(N,) stacked Fp12 products → (N,) bool through the standalone
        shared-inversion batched final-exp compile unit."""
        return self._final_exp_batch(fs)


class TpuBlsVerifier:
    """`IBlsVerifier`-shaped host API over the device kernels
    (reference: `chain/bls/interface.ts:20-46`).

    verify_signature_sets(sets) — all-or-nothing batch verdict.
    verify_signature_sets_individual(sets) — per-set verdicts (retry path).

    Semantics match the reference/eth2: infinity pubkeys or signatures,
    malformed encodings, or failed subgroup checks → False (without
    raising), exactly like `maybeBatch.ts` catching blst errors.
    """

    def __init__(
        self,
        buckets: tuple[int, ...] = (4, 16, 64, 128),
        rng=None,
        grouped_configs: tuple[tuple[int, int], ...] = ((16, 8), (64, 64)),
        device_decompress: bool | None = None,
        pk_grouped_configs: tuple[tuple[int, int], ...] = ((128, 32),),
        observer=None,
        mesh="auto",
    ):
        self.kernels = BatchVerifier(buckets, grouped_configs, pk_grouped_configs)
        # pipeline telemetry (observability.stages.PipelineMetrics): stage
        # timers, planner counters, cache hit rates. Node wiring passes the
        # /metrics-registered instance; the default keeps bench/tools lit.
        self.observer = observer if observer is not None else default_pipeline()
        self._custom_rng = rng
        self._rng = rng if rng is not None else (lambda: secrets.randbits(R_BITS))
        # hash-to-curve cache keyed by signing root: committee gossip
        # shares roots (every member of a committee signs the same data),
        # so H(m) recomputation dominates marshalling without this.
        # Insertion-ordered dict as LRU-ish FIFO, bounded; the lock covers
        # the get/evict/insert sequence — gossip threads and the block
        # import pool hit one shared verifier concurrently.
        import threading

        self._h2c_cache: dict[bytes, tuple] = {}
        self._h2c_cache_max = 8192
        self._h2c_lock = threading.Lock()
        # pubkey-limb cache: attesters repeat every epoch, so the per-set
        # G1 decompression (one Fp sqrt, ~0.2 ms C-tier) is redundant
        # steady-state work. The reference holds decompressed pubkeys in
        # its Index2PubkeyCache for exactly this reason (worker.ts
        # "deserializes affine without re-checking"). Bounded FIFO like
        # the h2c cache. Each entry is ONE packed (2·N_LIMBS,) int32
        # array (x‖y) — 256 B of limb data + one ndarray header + dict
        # slot + 48-B key ≈ 550 B/entry, so the 2^21 default costs
        # ~1.1 GB host RAM and holds every active mainnet validator with
        # headroom — a cap BELOW the active set would thrash to 0% hits
        # at exactly the target scale. Smaller hosts should set
        # LODESTAR_TPU_PK_CACHE_MAX (2^20 ≈ 0.55 GB still covers 1M).
        from ..utils.env import env_bool, env_int

        self._pk_cache: dict[bytes, "np.ndarray"] = {}  # guarded-by: _pk_lock
        self._pk_cache_max = env_int("LODESTAR_TPU_PK_CACHE_MAX")
        self._pk_lock = threading.Lock()
        # On-device signature decompression + batched plane subgroup
        # checks (ops/g2_decompress): removes the ~0.6 ms/set C-tier
        # signature marshal — the e2e floor on few-core hosts (VERDICT
        # r4 #5). DEFAULT-ON since round 6 (VERDICT r5 #4: the round's
        # biggest e2e win shipped off by default): the differential
        # coverage (tests/test_ops_decompress.py, the raw-kernel twins in
        # tests/test_parallel_verifier.py) is the same evidence the limb
        # kernels rest on. Constructor arg wins, then
        # LODESTAR_TPU_DEVICE_DECOMPRESS=0 as the off-switch (hosts with
        # cores to spare can keep the C tier); batches the native tier
        # can't marshal fall back to the host path automatically
        # (`_native_eligible` gates every raw dispatch).
        if device_decompress is None:
            device_decompress = env_bool("LODESTAR_TPU_DEVICE_DECOMPRESS")
        self._device_decompress = bool(device_decompress)
        # Mesh serving (round 7): grouped/pk-grouped/bisect batches
        # dispatch across every visible chip via parallel/mesh. The
        # default "auto" policy (env LODESTAR_TPU_MESH) enables the mesh
        # only on real multi-chip hardware — virtual CPU meshes are
        # opt-in ("force") because their chips share host cores. Pass a
        # BlsMeshDispatcher for explicit control, or mesh=None to pin
        # single-device dispatch.
        if mesh == "auto":
            from .mesh import auto_mesh

            self._mesh = auto_mesh(self.observer)
        else:
            self._mesh = mesh or None
        # Epoch-scoped pubkey table (ISSUE 18): committees are fixed per
        # epoch, so node.py pre-populates decompressed G1 limbs for the
        # whole active set at epoch transition; `_pk_rows` consults the
        # table before paying the C-tier sqrt, and the bounded `_pk_cache`
        # above stays as the fallback for keys the table never saw.
        if env_bool("LODESTAR_TPU_EPOCH_TABLE"):
            from .epoch_table import EpochPubkeyTable

            self._epoch_table = EpochPubkeyTable(observer=self.observer)
        else:
            self._epoch_table = None

    # -- ahead-of-time compilation -------------------------------------------

    PRECOMPILE_KINDS = ("grouped", "pk_grouped", "per_set", "bisect")

    def kernel_shapes(self, kinds=PRECOMPILE_KINDS, sharding=None):
        """(ledger-wrapped jit entry, argument shapes) for every kernel
        this verifier's shapes can dispatch on the paths in `kinds`:
        grouped (per grouped config), pk_grouped (per pk-grouped config),
        per_set (per bucket) and bisect (the per-set verdict tree per
        bucket, plus its probe). A shape the serving mesh takes is its
        sharded twin with inputs placed on the mesh, as dispatch routes
        it; the rest are single-device kernels on `sharding` (None = the
        default device). Shapes are `jax.ShapeDtypeStruct`s with the
        dtypes the marshallers produce."""
        k = self.kernels
        raw = self._device_decompress
        jobs = []

        def add(fn, shapes, mesh_kind=None, mesh_key=None):
            on = sharding
            if mesh_kind is not None and self._mesh_shardable(mesh_key[0]):
                fn, on = self._mesh.compile_target(mesh_kind, mesh_key)
            jobs.append((fn, tuple(
                jax.ShapeDtypeStruct(shape, dtype, sharding=on)
                for shape, dtype in shapes
            )))

        i32, u8, b = jnp.int32, jnp.uint8, jnp.bool_
        for kind, configs, fn, fn_raw in (
            ("grouped", k.grouped_configs, k._grouped, k._grouped_raw),
            ("pk_grouped", k.pk_grouped_configs, k._pk_grouped,
             k._pk_grouped_raw),
        ):
            if kind not in kinds:
                continue
            for r, l in configs:
                if kind == "grouped":
                    pk, msg = ((r, l, N_LIMBS), i32), ((r, 2, N_LIMBS), i32)
                else:
                    pk, msg = ((r, N_LIMBS), i32), ((r, l, 2, N_LIMBS), i32)
                bits, valid = ((r, l, HALF_BITS), i32), ((r, l), b)
                sig = ([((r, l, 96), u8)] if raw
                       else [((r, l, 2, N_LIMBS), i32)] * 2)
                add(fn_raw if raw else fn,
                    [pk, pk, msg, msg, *sig, bits, bits, valid],
                    kind + ("_raw" if raw else ""), (r, l))
        for n in k.buckets:
            pk, g2_ = ((n, N_LIMBS), i32), ((n, 2, N_LIMBS), i32)
            r_bits, valid = ((n, R_BITS), i32), ((n,), b)
            if "per_set" in kinds:
                sig = [((n, 96), u8)] if raw else [g2_, g2_]
                add(k._batch_raw if raw else k._batch,
                    [pk, pk, g2_, g2_, *sig, r_bits, valid])
            if "bisect" in kinds:
                # the sharded tree takes power-of-two batches only
                pow2 = not n & (n - 1)
                add(k._bisect_tree,
                    [pk, pk, g2_, g2_, g2_, g2_, r_bits, valid],
                    "bisect" if pow2 else None, (n,))
        if "bisect" in kinds:
            add(k._bisect_probe, [((PROBE_LANES, 2, 3, 2, N_LIMBS), i32)])
        return jobs

    def precompile(self) -> list[str]:
        """Compile every kernel this verifier can dispatch
        (`kernel_shapes()`) all at once on concurrent threads (XLA
        compiles release the GIL), before the first batch: each is
        minutes of compile for a TPU, and a first dispatch that compiles
        would blow the supervisor's per-dispatch deadline. Returns the
        compiled kernel names; each compile is a compile-ledger event."""
        from ..observability.compile_ledger import ledger

        jobs = self.kernel_shapes()
        led = ledger()
        run_concurrently([
            partial(led.compile_ahead, fn, *shapes) for fn, shapes in jobs
        ])
        return [fn.__compile_ledger_kernel__ for fn, _ in jobs]

    # -- mesh passthroughs (supervisor failure policy) ----------------------

    def mesh_evict(self, chip: int | None = None, reason: str = "failure"):
        """Evict a sick chip from the serving mesh; None when no mesh or
        nothing left to evict (the supervisor then falls back tiers)."""
        if self._mesh is None:
            return None
        return self._mesh.evict(chip=chip, reason=reason)

    def mesh_readmit(self) -> int:
        return 0 if self._mesh is None else self._mesh.readmit()

    def mesh_has_evicted(self) -> bool:
        return self._mesh is not None and self._mesh.has_evicted()

    def mesh_snapshot(self):
        return None if self._mesh is None else self._mesh.snapshot()

    def mesh_evict_host(self, host: int | None = None,
                        reason: str = "failure"):
        """Evict a whole host from the two-level serving fleet; None when
        no mesh / single-host census / nothing left to evict."""
        if self._mesh is None:
            return None
        return self._mesh.evict_host(host=host, reason=reason)

    def fleet_snapshot(self):
        return None if self._mesh is None else self._mesh.fleet_snapshot()

    def fleet_attach_router(self, router) -> None:
        """Bind the FleetRouter so host evictions rebalance its subnet
        slices (node wiring; no-op without a mesh)."""
        if self._mesh is not None:
            self._mesh.attach_router(router)

    # -- host marshalling ---------------------------------------------------

    def _native_eligible(self, sets) -> bool:
        from .. import native as _native

        return _native.HAVE_NATIVE_BLS and all(
            len(s.message) == 32 and len(s.signature) == 96 for s in sets
        )

    def _hash_root(self, key: bytes):
        """H(m) limbs for one 32-byte signing root via the bounded cache;
        None if the C tier rejects it."""
        from .. import native as _native

        cache = self._h2c_cache
        with self._h2c_lock:
            hit = cache.get(key)
        self.observer.cache_event("h2c", hit is not None)
        if hit is None:
            # hash OUTSIDE the lock (ms-scale C work, GIL released)
            with self.observer.stage("hash_to_curve"):
                rc, limbs = _native.bls_hash_to_g2(key, bls_api.DST_G2)
            if rc != 0:
                return None
            hit = (limbs[0], limbs[1])
            with self._h2c_lock:
                while len(cache) >= self._h2c_cache_max:
                    try:
                        cache.pop(next(iter(cache)))
                    except (StopIteration, KeyError):
                        break
                cache[key] = hit
        return hit

    def _pk_rows(self, sets):
        """(pk_x, pk_y) rows for every set via the pubkey-limb cache;
        None if any pubkey is malformed/infinity. Cache misses pay one
        C-tier G1 decompression each — once per validator, ever."""
        from .. import native as _native

        try:
            keys = [s.pubkey.to_bytes() for s in sets]
        except (bls_api.BlsError, ValueError):
            return None
        with self._pk_lock:
            rows = [self._pk_cache.get(k) for k in keys]
        misses = {k for k, r in zip(keys, rows) if r is None}
        self.observer.cache_event("pk", True, n=len(keys) - len(misses))
        self.observer.cache_event("pk", False, n=len(misses))
        if misses:
            fresh = {}
            # epoch table first: a hit is a memcpy off the host mirror
            # instead of a C-tier Fp sqrt (ISSUE 18)
            if self._epoch_table is not None:
                miss_keys = list(misses)
                for k, row in zip(
                    miss_keys, self._epoch_table.lookup_rows(miss_keys)
                ):
                    if row is not None:
                        fresh[k] = row
                        misses.discard(k)
            for k in misses:
                rc, limbs = _native.bls_g1_decompress(k, check_subgroup=False)
                if rc != 0:
                    return None  # infinity pubkey is invalid per Eth2
                fresh[k] = np.concatenate((limbs[0], limbs[1]))
            with self._pk_lock:
                cache = self._pk_cache
                for k, v in fresh.items():
                    while len(cache) >= self._pk_cache_max:
                        try:
                            cache.pop(next(iter(cache)))
                        except (StopIteration, KeyError):
                            break
                    cache[k] = v
            rows = [r if r is not None else fresh[k] for k, r in zip(keys, rows)]
        n = len(sets)
        pk_x = np.empty((n, N_LIMBS), np.int32)
        pk_y = np.empty((n, N_LIMBS), np.int32)
        for i, r in enumerate(rows):
            pk_x[i] = r[:N_LIMBS]
            pk_y[i] = r[N_LIMBS:]
        return pk_x, pk_y

    # -- epoch-scoped precomputation (ISSUE 18) -----------------------------

    def warm_h2c(self, messages) -> int:
        """Pre-warm the hash-to-curve cache for 32-byte signing roots —
        the dispatcher's H(msg) dedup seam: one hash_to_g2 per UNIQUE
        attestation data across a coalesced flush, after which the
        marshal path hits `_h2c_cache` for every duplicate. Returns the
        number of roots hashed (misses)."""
        hashed = 0
        for m in messages:
            if len(m) != 32:
                continue
            with self._h2c_lock:
                hit = m in self._h2c_cache
            if not hit:
                if self._hash_root(m) is not None:
                    hashed += 1
        return hashed

    def epoch_table_populate(self, epoch: int, pubkeys) -> int:
        """Install one epoch's device-resident pubkey table entry from an
        iterable of compressed pubkey bytes (node.py calls this at epoch
        transition with the active validator set). Decompression happens
        once per key here — off the dispatch path — reusing `_pk_cache`
        rows when present. Returns rows installed; 0 when the table is
        disabled or a key is malformed (population is best-effort: the
        dispatch path keeps its own fallbacks)."""
        from .. import native as _native

        if self._epoch_table is None:
            return 0
        items = []
        for k in pubkeys:
            k = bytes(k)
            with self._pk_lock:
                row = self._pk_cache.get(k)
            if row is None:
                rc, limbs = _native.bls_g1_decompress(k, check_subgroup=False)
                if rc != 0:
                    continue  # skip malformed/infinity, keep the rest
                row = np.concatenate((limbs[0], limbs[1]))
            items.append((k, row))
        return self._epoch_table.populate(epoch, items)

    def epoch_table_snapshot(self):
        """Epoch-table state for `/debug/epoch_table`; {"enabled": False}
        when LODESTAR_TPU_EPOCH_TABLE is off."""
        if self._epoch_table is None:
            return {"enabled": False}
        snap = self._epoch_table.snapshot()
        snap["enabled"] = True
        return snap

    def _native_limbs(self, sets):
        """Per-set (pk_x, pk_y, sig_x, sig_y) limb arrays via the C tier
        (decompress + subgroup checks, no hashing); None if any set is
        malformed, out of subgroup, or at infinity.

        Pubkeys come from the limb cache (`_pk_rows`); only signatures
        pay the per-set decompression. Large batches are chunked across
        the marshalling pool: the C tier releases the GIL, so threads
        scale with cores (the reference sizes its worker pool the same
        way — `chain/bls/multithread/poolSize.ts`)."""
        from .. import native as _native

        pk_rows = self._pk_rows(sets)
        if pk_rows is None:
            return None
        pk_x, pk_y = pk_rows
        n = len(sets)
        pk_b = b"\x00" * (48 * n)  # unused: do_pk=False
        msg_b = b"".join(s.message for s in sets)
        sig_b = b"".join(s.signature for s in sets)

        pool = _marshal_pool()
        if pool is None or n < 2 * _MARSHAL_CHUNK:
            _px, _py, _mx, _my, sig_x, sig_y, ok = _native.bls_marshal_sets(
                pk_b, msg_b, sig_b, bls_api.DST_G2, do_hash=False, do_pk=False
            )
            if not ok.all():
                return None
            return pk_x, pk_y, sig_x, sig_y

        def chunk(lo: int, hi: int):
            return _native.bls_marshal_sets(
                pk_b[48 * lo : 48 * hi],
                msg_b[32 * lo : 32 * hi],
                sig_b[96 * lo : 96 * hi],
                bls_api.DST_G2,
                do_hash=False,
                do_pk=False,
            )

        bounds = list(range(0, n, _MARSHAL_CHUNK)) + [n]
        futs = [
            pool.submit(chunk, lo, hi)
            for lo, hi in zip(bounds[:-1], bounds[1:])
        ]
        parts = [f.result() for f in futs]
        if not all(p[6].all() for p in parts):
            return None
        sig_x = np.concatenate([p[4] for p in parts])
        sig_y = np.concatenate([p[5] for p in parts])
        return pk_x, pk_y, sig_x, sig_y

    def _split_shared_unique(self, sets):
        """Partition set indices into (shared-root, singleton-root).

        The adversarial-mix defense (VERDICT r3 #1): an attacker minting
        unique `AttestationData` must not drag the whole batch onto the
        per-set kernel — honest committee traffic (shared roots) keeps
        the grouped fast path; only the attacker's singletons pay the
        per-set rate."""
        from collections import Counter

        freq = Counter(s.message for s in sets)
        shared = [i for i, s in enumerate(sets) if freq[s.message] >= 2]
        unique = [i for i, s in enumerate(sets) if freq[s.message] < 2]
        return shared, unique

    @staticmethod
    def _plan_runs(keys, configs):
        """Shared run-packing for both grouping axes: pack items into
        per-key runs of ≤ lane_cap, ≤ rows_cap runs total; None when no
        config fits or fewer than half the items share keys."""
        uniq = len(set(keys))
        if uniq * 2 > len(keys):
            return None
        for rows_cap, lane_cap in configs:
            if len(keys) > rows_cap * lane_cap:
                continue
            runs: list[list[int]] = []
            open_run: dict[bytes, list[int]] = {}
            fits = True
            for idx, key in enumerate(keys):
                run = open_run.get(key)
                if run is not None and len(run) < lane_cap:
                    run.append(idx)
                else:
                    run = [idx]
                    runs.append(run)
                    open_run[key] = run
                    if len(runs) > rows_cap:
                        fits = False
                        break
            if fits:
                return rows_cap, lane_cap, runs
        return None

    def _plan_groups(self, sets):
        """Choose a grouped-kernel config + row assignment, or None for the
        flat path. Grouping pays when roots are shared (committee gossip);
        a mostly-unique batch stays on the per-set kernel."""
        return self._plan_runs(
            [s.message for s in sets], self.kernels.grouped_configs
        )

    def _marshal_grouped(self, sets, plan, raw: bool = False):
        """Scatter sets into (rows × lanes) by signing root; None if any
        set is invalid (the caller reports False, same as `_marshal`).

        raw=False: C-tier signature decompression → GroupedArrays.
        raw=True: signatures stay BYTES for the device decode path →
        (GroupedArrays with sig_* zeroed, sig_raw (R, L, 96) uint8)."""
        rows_cap, lane_cap, runs = plan
        if raw:
            pk_rows = self._pk_rows(sets)
            if pk_rows is None:
                return None
            pk_x, pk_y = pk_rows
            sig_all = np.frombuffer(
                b"".join(s.signature for s in sets), np.uint8
            ).reshape(len(sets), 96)
            sig_raw = np.zeros((rows_cap, lane_cap, 96), np.uint8)
        else:
            limbs = self._native_limbs(sets)
            if limbs is None:
                return None
            pk_x, pk_y, sig_x, sig_y = limbs
        g = GroupedArrays(rows_cap, lane_cap)
        for row, run in enumerate(runs):
            hit = self._hash_root(sets[run[0]].message)
            if hit is None:
                return None
            g.msg_x[row], g.msg_y[row] = hit
            idx = np.asarray(run)
            k = len(run)
            g.pk_x[row, :k], g.pk_y[row, :k] = pk_x[idx], pk_y[idx]
            if raw:
                sig_raw[row, :k] = sig_all[idx]
            else:
                g.sig_x[row, :k], g.sig_y[row, :k] = sig_x[idx], sig_y[idx]
            g.valid[row, :k] = True
        g.n = len(sets)
        return (g, sig_raw) if raw else g

    def _plan_pk_groups(self, sets):
        """Choose a pk-grouped config + row assignment, or None. Pays when
        pubkeys repeat while roots do not (attacker-minted unique
        AttestationData — the adversarial shape; VERDICT r4 #2)."""
        try:
            keys = [s.pubkey.to_bytes() for s in sets]
        except (bls_api.BlsError, ValueError):
            return None  # flat path reports the malformed set as False
        return self._plan_runs(keys, self.kernels.pk_grouped_configs)

    def _marshal_pk_grouped(self, sets, plan, raw: bool = False):
        """Scatter sets into (rows × lanes) by pubkey; None if any set is
        invalid. raw=True keeps signatures as bytes for the device.

        This path's target workload is all-UNIQUE roots (the adversarial
        flood), so the h2c cache never hits — messages are hashed through
        the marshal pool in chunks (the C tier releases the GIL; hashing
        scales with host cores like the reference's worker pool)."""
        rows_cap, lane_cap, runs = plan
        if raw:
            pk_rows = self._pk_rows(sets)
            if pk_rows is None:
                return None
            pk_x, pk_y = pk_rows
            sig_all = np.frombuffer(
                b"".join(s.signature for s in sets), np.uint8
            ).reshape(len(sets), 96)
            sig_raw = np.zeros((rows_cap, lane_cap, 96), np.uint8)
        else:
            limbs = self._native_limbs(sets)
            if limbs is None:
                return None
            pk_x, pk_y, sig_x, sig_y = limbs
        # pooled hash-to-curve over the (mostly-unique) roots
        pool = _marshal_pool()
        hits: list = [None] * len(sets)
        if pool is not None and len(sets) >= 2 * _MARSHAL_CHUNK:
            def hash_chunk(lo, hi):
                return [self._hash_root(s.message) for s in sets[lo:hi]]

            bounds = list(range(0, len(sets), _MARSHAL_CHUNK)) + [len(sets)]
            futs = [
                pool.submit(hash_chunk, lo, hi)
                for lo, hi in zip(bounds[:-1], bounds[1:])
            ]
            out = []
            for f in futs:
                out.extend(f.result())
            hits = out
        else:
            hits = [self._hash_root(s.message) for s in sets]
        if any(h is None for h in hits):
            return None
        g = PkGroupedArrays(rows_cap, lane_cap)
        for row, run in enumerate(runs):
            g.pk_x[row], g.pk_y[row] = pk_x[run[0]], pk_y[run[0]]
            for j, idx in enumerate(run):
                g.msg_x[row, j], g.msg_y[row, j] = hits[idx]
            idxs = np.asarray(run)
            k = len(run)
            if raw:
                sig_raw[row, :k] = sig_all[idxs]
            else:
                g.sig_x[row, :k], g.sig_y[row, :k] = sig_x[idxs], sig_y[idxs]
            g.valid[row, :k] = True
        g.n = len(sets)
        return (g, sig_raw) if raw else g

    def _submit_pk_grouped_mesh(self, sets, plan):
        """Sharded pk-grouped dispatch (raw wire-byte signatures when
        device decompression is on — see `_submit_grouped_mesh`)."""
        from .mesh import NOT_SHARDED

        if self._device_decompress:
            with self.observer.stage("marshal"):
                marshalled = self._marshal_pk_grouped(sets, plan, raw=True)
            if marshalled is None:
                return None
            g, sig_raw = marshalled
            with self.observer.stage("rand"):
                a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
            with self.observer.stage("dispatch"):
                result = self._mesh.dispatch_pk_grouped_raw(
                    g, sig_raw, a_bits, b_bits
                )
                if result is NOT_SHARDED:
                    result = self.kernels.verify_pk_grouped_raw(
                        g, sig_raw, a_bits, b_bits
                    )
            return result
        with self.observer.stage("marshal"):
            g = self._marshal_pk_grouped(sets, plan)
        if g is None:
            return None
        with self.observer.stage("rand"):
            a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
        with self.observer.stage("dispatch"):
            result = self._mesh.dispatch_pk_grouped(g, a_bits, b_bits)
            if result is NOT_SHARDED:
                result = self.kernels.verify_pk_grouped(g, a_bits, b_bits)
        return result

    def _submit_pk_grouped(self, sets, plan):
        """Dispatch one pk-grouped batch; None marks an invalid set."""
        self.observer.planner(
            "pk_grouped", len(sets), group_sizes=[len(r) for r in plan[2]]
        )
        if self._mesh_shardable(plan[0]):
            return self._submit_pk_grouped_mesh(sets, plan)
        if self._device_decompress:
            with self.observer.stage("marshal"):
                marshalled = self._marshal_pk_grouped(sets, plan, raw=True)
            if marshalled is None:
                return None
            g, sig_raw = marshalled
            with self.observer.stage("rand"):
                a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
            with self.observer.stage("dispatch"):
                return self.kernels.verify_pk_grouped_raw(
                    g, sig_raw, a_bits, b_bits
                )
        with self.observer.stage("marshal"):
            g = self._marshal_pk_grouped(sets, plan)
        if g is None:
            return None
        with self.observer.stage("rand"):
            a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
        with self.observer.stage("dispatch"):
            return self.kernels.verify_pk_grouped(g, a_bits, b_bits)

    def _marshal(self, sets, raw: bool = False):
        """Build padded device arrays; None if any set is invalid up front.

        Fast path: the native C tier (`native/src/bls12.c`) decompresses,
        subgroup-checks and hash-to-curves the whole batch in one call —
        the reference keeps exactly this preprocessing in blst C
        (multithread/worker.ts:33-55). Falls back to the big-int oracle
        when the extension is unavailable.

        raw=True: signatures stay BYTES for the device decode path →
        (SetArrays with sig_* zeroed, sig_raw (lanes, 96) uint8).
        """
        if not sets:
            return None
        lanes = self.kernels.bucket_for(len(sets))
        if len(sets) > lanes:
            return None  # caller must chunk (service layer's job)

        if raw:
            pk_rows = self._pk_rows(sets)
            if pk_rows is None:
                return None
            pk_x, pk_y = pk_rows
            arrs = SetArrays(lanes)
            sig_raw = np.zeros((lanes, 96), np.uint8)
            n = len(sets)
            arrs.pk_x[:n], arrs.pk_y[:n] = pk_x, pk_y
            sig_raw[:n] = np.frombuffer(
                b"".join(s.signature for s in sets), np.uint8
            ).reshape(n, 96)
            for i, s in enumerate(sets):
                hit = self._hash_root(s.message)
                if hit is None:
                    return None
                arrs.msg_x[i], arrs.msg_y[i] = hit
            arrs.valid[:n] = True
            arrs.n = n
            return arrs, sig_raw

        if self._native_eligible(sets):
            limbs = self._native_limbs(sets)
            if limbs is None:
                return None
            pk_x, pk_y, sig_x, sig_y = limbs
            arrs = SetArrays(lanes)
            n = len(sets)
            arrs.pk_x[:n], arrs.pk_y[:n] = pk_x, pk_y
            arrs.sig_x[:n], arrs.sig_y[:n] = sig_x, sig_y
            for i, s in enumerate(sets):
                hit = self._hash_root(s.message)
                if hit is None:
                    return None
                arrs.msg_x[i], arrs.msg_y[i] = hit
            arrs.valid[:n] = True
            arrs.n = n
            return arrs
        arrs = SetArrays(lanes)
        for i, s in enumerate(sets):
            if s.pubkey.point.is_infinity():
                return None
            try:
                sig = bls_api.Signature.from_bytes(s.signature).point
            except (bls_api.BlsError, ValueError):
                return None
            if sig.is_infinity():
                return None
            arrs.pk_x[i], arrs.pk_y[i], _ = g1_affine_to_limbs(s.pubkey.point)
            h = hash_to_g2(s.message)
            arrs.msg_x[i], arrs.msg_y[i], _ = g2_affine_to_limbs(h)
            arrs.sig_x[i], arrs.sig_y[i], _ = g2_affine_to_limbs(sig)
            arrs.valid[i] = True
        arrs.n = len(sets)
        return arrs

    # -- public API ---------------------------------------------------------

    def verify_signature_sets(self, sets) -> bool:
        return self.verify_signature_sets_submit(sets)()

    def verify_signature_sets_submit(self, sets):
        """Marshal on the host NOW, dispatch to the device NOW, block
        LATER: returns a zero-arg resolver for the verdict.

        The device computes while the caller marshals its next batch —
        the double-buffering the reference gets from its worker pool
        (main thread aggregates the next job while workers verify,
        `chain/bls/interface.ts:30-35`). `verify_signature_sets` is
        submit-then-resolve with no batch behind it."""
        # fault-injection seam (testing.faults): no-op unless a plan is
        # armed via LODESTAR_TPU_FAULTS or /debug/faults — the supervisor
        # tier's failure policy is exercised against exactly this boundary
        _faults.on_device_dispatch(len(sets))
        if sets and self._native_eligible(sets):
            plan = self._plan_groups(sets)
            if plan is not None:
                t = time.monotonic()
                result = self._submit_grouped(sets, plan)
                if result is None:
                    return lambda: False
                return lambda: self._resolve(result, t)
            # roots don't group — try the DUAL axis: pubkeys repeat in
            # any adversarial unique-root flood (bounded attacker keys)
            pk_plan = self._plan_pk_groups(sets)
            if pk_plan is not None:
                t = time.monotonic()
                result = self._submit_pk_grouped(sets, pk_plan)
                if result is None:
                    return lambda: False
                return lambda: self._resolve(result, t)
            # mixed batch: peel the shared-root sets onto the grouped
            # kernel; the singleton remainder tries pk-grouping before
            # paying the per-set kernel
            shared, unique = self._split_shared_unique(sets)
            if shared and unique:
                shared_sets = [sets[i] for i in shared]
                sub_plan = self._plan_groups(shared_sets)
                if sub_plan is not None:
                    # the peeled parts also count under their own paths
                    self.observer.planner("split", len(sets))
                    t = time.monotonic()
                    grouped_res = self._submit_grouped(shared_sets, sub_plan)
                    if grouped_res is None:
                        return lambda: False
                    unique_sets = [sets[i] for i in unique]
                    pk_plan = self._plan_pk_groups(unique_sets)
                    if pk_plan is not None:
                        pk_res = self._submit_pk_grouped(unique_sets, pk_plan)
                        if pk_res is None:
                            return lambda: False
                        return lambda: (
                            self._resolve(grouped_res, t)
                            and self._resolve(pk_res, t)
                        )
                    flat = self._submit_flat(unique_sets)
                    return lambda: self._resolve(grouped_res, t) and flat()
        return self._submit_flat(sets)

    def _resolve(self, result, t_submit: float | None = None) -> bool:
        """Block on one device verdict, timing the wait (`device_wait`
        stage) and feeding the busy-fraction sampler with the full
        submit→resolve span (the device computes through the async gap,
        so resolver block time alone undercounts occupancy)."""
        t0 = time.monotonic()
        with self.observer.stage("device_wait"):
            verdict = bool(result)
        self.observer.device_busy_sample(
            time.monotonic() - (t_submit if t_submit is not None else t0)
        )
        # flaky-verdict injection (testing.faults): True -> False only,
        # modeling corrupted device computation
        return _faults.flaky_verdict(verdict)

    def _mesh_shardable(self, rows: int) -> bool:
        return (
            self._mesh is not None
            and self._mesh.enabled
            and rows % self._mesh.size == 0
        )

    def _submit_grouped_mesh(self, sets, plan):
        """Sharded grouped dispatch across the serving mesh. With device
        decompression on (the default), signatures stay WIRE BYTES all
        the way onto the mesh — the `*_raw` sharded twins decode each
        chip's row slice on device, so the host marshal is a pure byte
        scatter (zero-copy ingest, same contract as the single-device
        raw path). LODESTAR_TPU_DEVICE_DECOMPRESS=0 keeps the pooled
        C-tier limb marshal. Falls back to the matching single-device
        kernel if the mesh shrank between the eligibility check and the
        dispatch."""
        from .mesh import NOT_SHARDED

        if self._device_decompress:
            with self.observer.stage("marshal"):
                marshalled = self._marshal_grouped(sets, plan, raw=True)
            if marshalled is None:
                return None
            g, sig_raw = marshalled
            with self.observer.stage("rand"):
                a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
            with self.observer.stage("dispatch"):
                result = self._mesh.dispatch_grouped_raw(
                    g, sig_raw, a_bits, b_bits
                )
                if result is NOT_SHARDED:
                    result = self.kernels.verify_grouped_raw(
                        g, sig_raw, a_bits, b_bits
                    )
            return result
        with self.observer.stage("marshal"):
            g = self._marshal_grouped(sets, plan)
        if g is None:
            return None
        with self.observer.stage("rand"):
            a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
        with self.observer.stage("dispatch"):
            result = self._mesh.dispatch_grouped(g, a_bits, b_bits)
            if result is NOT_SHARDED:
                result = self.kernels.verify_grouped(g, a_bits, b_bits)
        return result

    def _submit_grouped(self, sets, plan):
        """Dispatch one grouped-kernel batch; None marks an invalid set
        (caller reports False)."""
        self.observer.planner(
            "root_grouped", len(sets), group_sizes=[len(r) for r in plan[2]]
        )
        if self._mesh_shardable(plan[0]):
            return self._submit_grouped_mesh(sets, plan)
        if self._device_decompress:
            with self.observer.stage("marshal"):
                marshalled = self._marshal_grouped(sets, plan, raw=True)
            if marshalled is None:
                return None
            g, sig_raw = marshalled
            with self.observer.stage("rand"):
                a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
            with self.observer.stage("dispatch"):
                return self.kernels.verify_grouped_raw(
                    g, sig_raw, a_bits, b_bits
                )
        with self.observer.stage("marshal"):
            g = self._marshal_grouped(sets, plan)
        if g is None:
            return None
        with self.observer.stage("rand"):
            a_bits, b_bits = _rand_pairs(g.valid.shape, self._custom_rng)
        with self.observer.stage("dispatch"):
            return self.kernels.verify_grouped(g, a_bits, b_bits)

    def _submit_flat(self, sets):
        """Per-set kernel dispatch (chunked to the largest bucket);
        resolver ANDs the chunk verdicts — all-or-nothing, same as one
        dispatch."""
        if sets:
            self.observer.planner("per_set", len(sets))
        cap = self.kernels.buckets[-1]
        use_raw = self._device_decompress and self._native_eligible(sets)
        results = []
        t_submit = time.monotonic()
        for lo in range(0, max(len(sets), 1), cap):
            chunk = sets[lo : lo + cap]
            if use_raw:
                with self.observer.stage("marshal"):
                    marshalled = self._marshal(chunk, raw=True)
                if marshalled is None:
                    return lambda: False
                arrs, sig_raw = marshalled
                with self.observer.stage("rand"):
                    r_bits = _rand_bits(arrs.pk_x.shape[0], self._rng)
                with self.observer.stage("dispatch"):
                    results.append(
                        self.kernels.verify_batch_raw(arrs, sig_raw, r_bits)
                    )
                continue
            with self.observer.stage("marshal"):
                arrs = self._marshal(chunk)
            if arrs is None:
                return lambda: False
            with self.observer.stage("rand"):
                r_bits = _rand_bits(arrs.pk_x.shape[0], self._rng)
            with self.observer.stage("dispatch"):
                results.append(self.kernels.verify_batch(arrs, r_bits))
        return lambda: all(self._resolve(r, t_submit) for r in results)

    def verify_signature_sets_individual(self, sets) -> list[bool]:
        """Per-set verdicts via BISECTION (round-6 tentpole): one
        randomized product-tree dispatch decides the all-valid common
        case with a single final exponentiation; on failure the
        materialized internal nodes are binary-searched so k invalid
        sets cost O(k·log N) batched probe final exps instead of N
        (`individual_verify_kernel`'s price). Leaf probes are exact, so
        verdicts match the old kernel (and the CPU oracle) bit-for-bit;
        internal short-circuits carry the same 2^-64 soundness as batch
        verification itself."""
        self.observer.planner("individual", len(sets))
        _faults.on_device_dispatch(len(sets))
        with self.observer.stage("marshal"):
            arrs = self._marshal(sets)
        if arrs is None:
            # mirror reference behavior: individually report malformed as False
            return [self._verify_one(s) for s in sets]
        with self.observer.stage("rand"):
            r_bits = _rand_bits(arrs.pk_x.shape[0], self._rng)
        t = time.monotonic()
        with self.observer.stage("dispatch"):
            sharded = None
            if self._mesh is not None and self._mesh.enabled:
                from .mesh import NOT_SHARDED

                sharded = self._mesh.dispatch_bisect(arrs, r_bits)
                if sharded is NOT_SHARDED:
                    sharded = None
            if sharded is not None:
                root_ok, levels = sharded
            else:
                root_ok, levels = self.kernels.verify_bisect_tree(arrs, r_bits)
        with self.observer.stage("device_wait"):
            root_ok = bool(root_ok)
        self.observer.device_busy_sample(time.monotonic() - t)
        if root_ok:
            self.observer.bisect(rounds=0, probes=0)
            return _faults.flaky_verdicts([True] * arrs.n)
        verdicts = self._bisect(arrs, levels)
        return _faults.flaky_verdicts([bool(v) for v in verdicts[: arrs.n]])

    def _bisect(self, arrs, levels) -> np.ndarray:
        """Binary-search a failed product tree for the invalid leaves.

        levels[j] holds M >> j nodes; node (j, i) covers leaves
        [i·2^j, (i+1)·2^j). BFS from the root: every round probes the
        children of the currently-failed nodes — all probes of a round
        ride ONE fixed-shape batched final exp (PROBE_LANES lanes,
        identity-padded, shared easy-part inversion), so a round is one
        dispatch until k grows past PROBE_LANES/2. A child that passes
        clears its whole subtree (2^-64 soundness per probe); failed
        level-0 nodes are the invalid sets, exactly.

        Freak outcome — a failed parent with two passing children (a
        2^-64 cancellation): fall back to the exact per-set kernel
        rather than return an inconsistent verdict vector."""
        levels_np = [np.asarray(l) for l in levels]
        m = levels_np[0].shape[0]
        verdicts = np.ones(m, bool)
        verdicts[arrs.n:] = False  # padding lanes report False
        frontier = [(len(levels_np) - 1, 0)]
        rounds = probes = 0
        global _FP12_ONE_NP
        if _FP12_ONE_NP is None:
            _FP12_ONE_NP = np.asarray(fp12.one(()))
        while frontier:
            if frontier[0][0] == 0:
                for _, i in frontier:
                    verdicts[i] = False
                break
            rounds += 1
            children = [
                (lvl - 1, 2 * i + k) for lvl, i in frontier for k in (0, 1)
            ]
            failed = []
            for lo in range(0, len(children), PROBE_LANES):
                chunk = children[lo : lo + PROBE_LANES]
                batch = np.stack([levels_np[l][i] for l, i in chunk])
                if len(chunk) < PROBE_LANES:
                    pad = np.broadcast_to(
                        _FP12_ONE_NP,
                        (PROBE_LANES - len(chunk),) + _FP12_ONE_NP.shape,
                    )
                    batch = np.concatenate([batch, pad])
                t0 = time.monotonic()
                with self.observer.stage("bisect"):
                    out = np.asarray(self.kernels.probe_nodes(batch))
                self.observer.device_busy_sample(time.monotonic() - t0)
                probes += len(chunk)
                failed.extend(
                    node for node, ok in zip(chunk, out[: len(chunk)])
                    if not ok
                )
            if not failed:
                # 2^-64 cancellation inside a subtree: exact fallback
                self.observer.bisect(rounds=rounds, probes=probes)
                out = np.asarray(self.kernels.verify_individual(arrs))
                return out
            frontier = failed
        self.observer.bisect(rounds=rounds, probes=probes)
        return verdicts

    def _verify_one(self, s) -> bool:
        try:
            arrs = self._marshal([s])
        except (bls_api.BlsError, ValueError):
            return False
        if arrs is None:
            return False
        return bool(np.asarray(self.kernels.verify_individual(arrs))[0])
