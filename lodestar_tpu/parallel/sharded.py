"""Mesh-sharded batch verification: the ICI-collective tier.

Where the reference scales BLS batch verification by chunking jobs across
`num_cpus` worker threads (`chain/bls/multithread/index.ts:153-166`,
`poolSize.ts`), this module shards ONE batch across all chips of a
`jax.sharding.Mesh` with `shard_map`:

- every chip runs scalar-muls + Miller loops for its slice of the batch
  (pure data parallelism over the 'dp' axis — zero communication),
- the G2 aggregated-signature sum and the Fp12 pair-product are combined
  with a single `all_gather` each over ICI (small payloads: one projective
  G2 point and one Fp12 element per chip), and the tiny cross-chip tail
  reduction plus the final exponentiation run replicated.

DCN enters when the mesh spans hosts (ROADMAP item 5, fleet serving):
every kernel here also compiles over a TWO-LEVEL mesh — `axis` may be a
tuple ``(dcn_axis, ici_axis)`` naming the outer cross-host axis and the
inner within-host axis of a 2-D `Mesh`. The combines are then
HIERARCHICAL and ICI-first: per-chip partials (Fp12 pair products, G2
bit-plane sums) all_gather over ICI and reduce to ONE per-host value
before a second all_gather crosses DCN — so the slow inter-host fabric
carries one Fp12 element / 64 combined plane sums per HOST, never
per-chip traffic. Per-chip Horner tails and Miller lanes stay ICI-local
either way (pure data parallelism; the linear chip index is DCN-major,
matching the `P((dcn, ici))` row sharding).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.trace import named_scope
from ..ops import fp, fp2, fp12, msm
from ..ops.g2_decompress import (
    decompress as _g2_decompress,
    planes_in_subgroup as _planes_in_subgroup,
)


def _shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off: the kernels gather
    partials with explicit collectives and return replicated verdicts."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _axes(mesh_axis) -> tuple:
    """Normalize an axis spec to a tuple of axis names: ``"dp"`` →
    ``("dp",)``; a two-level ``("dcn", "ici")`` passes through with the
    OUTER (cross-host) axis first — the same order as the 2-D Mesh's
    axis_names and the `P((dcn, ici))` input sharding."""
    return (mesh_axis,) if isinstance(mesh_axis, str) else tuple(mesh_axis)


def _mesh_size(mesh_axis):
    """Total chip count across all (1 or 2) mesh axes."""
    n = 1
    for name in _axes(mesh_axis):
        n = n * lax.axis_size(name)
    return n


def _mesh_index(mesh_axis):
    """This chip's linear index over the (possibly two-level) mesh,
    row-major with the DCN axis slowest — matching the `P((dcn, ici))`
    row sharding, so chip k owns global row-block k. Index 0 (host 0,
    chip 0) is the root-tail owner."""
    idx = 0
    for name in _axes(mesh_axis):
        idx = idx * lax.axis_size(name) + lax.axis_index(name)
    return idx


def _gather_fp12_partials(f_loc, mesh_axis):
    """Gather the per-chip Fp12 pair-product partials for the root tail.
    Single-level: one all_gather over ICI → (ndev, …). Two-level: gather
    over ICI first and reduce to the per-host product, so DCN carries
    exactly ONE Fp12 element per host → (hosts, …)."""
    axes = _axes(mesh_axis)
    if len(axes) == 1:
        return lax.all_gather(f_loc, axes[0])
    dcn, ici = axes
    f_host = _fp12_product_tree(lax.all_gather(f_loc, ici))
    return lax.all_gather(f_host, dcn)


def _combine_plane_sums(u_part, mesh_axis):
    """Combine per-chip partial G2 bit-plane sums into the replicated
    (64,) totals. Hierarchical and ICI-first on a two-level mesh: the
    inner gather + tree_sum collapses each host to one set of 64 plane
    sums before the outer (DCN) gather — per-host-combined sums are the
    only plane traffic that crosses hosts."""
    u = u_part
    for name in reversed(_axes(mesh_axis)):
        u_all = tuple(lax.all_gather(c, name) for c in u)  # (n, 64, …)
        u_all = tuple(jnp.moveaxis(c, 0, 1) for c in u_all)  # (64, n, …)
        u = msm.tree_sum(g2, u_all)
    return u
from ..ops.pairing import (
    final_exponentiation_one,
    miller_loop_proj_pq,
    miller_loop_projective,
)
from ..ops.points import (
    G1_GEN_X,
    G1_GEN_Y,
    NEG_G1_POW2_X,
    NEG_G1_POW2_Y,
    g1,
    g2,
    g2_psi,
)
from .verifier import HALF_BITS, N_LIMBS, _fp12_product_tree, _g2_sum_tree

__all__ = [
    "mesh_divisor",
    "make_sharded_verifier",
    "ShardedBlsVerifier",
    "make_sharded_grouped_verifier",
    "ShardedGroupedVerifier",
    "make_sharded_grouped_raw_verifier",
    "ShardedGroupedRawVerifier",
    "make_sharded_pk_grouped_verifier",
    "ShardedPkGroupedVerifier",
    "make_sharded_pk_grouped_raw_verifier",
    "ShardedPkGroupedRawVerifier",
    "make_sharded_bisect_verifier",
    "ShardedBisectVerifier",
]


# host-side mesh sizing lives in the jax-free policy module; re-exported
# here because every sharded-kernel consumer needs it for shape planning
from .mesh import mesh_divisor  # noqa: E402  (after the jax imports above)


def _local_body(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
    """Per-chip slice of the batch equation; returns (local Fp12 pair
    product, local partial G2 signature sum) — the two values that cross
    the ICI boundary."""
    n_loc = pk_x.shape[0]
    rpk = g1.scalar_mul_bits(r_bits, (pk_x, pk_y))
    rsig = g2.scalar_mul_bits(r_bits, (sig_x, sig_y))
    rsig = g2.select(valid, rsig, g2.infinity((n_loc,)))
    s_part = _g2_sum_tree(rsig)

    fs = miller_loop_projective(rpk, (msg_x, msg_y))
    fs = fp12.select(valid, fs, fp12.one((n_loc,)))
    return _fp12_product_tree(fs), s_part


def _tail_on_root(mesh_axis, tail_fn):
    """Run the sequential tail on chip 0 only and broadcast the verdict.

    The tail (G2 affine inversion, one Miller lane, the final
    exponentiation) is a latency-bound chain that cannot shard; running
    it REPLICATED makes every chip burn the same wall-clock — harmless on
    idle real chips but disastrous on a virtual CPU mesh where all
    "devices" share host cores (round-3 MESH_SCALING regressed 145 → 66
    sets/s from exactly this). Chip 0 computes, the rest contribute a
    zero to the psum — the reference's analog is the main thread owning
    aggregation while workers verify (`chain/bls/multithread/index.ts`).

    On a two-level mesh the root is linear chip 0 = (host 0, chip 0) and
    the verdict psum spans both axes (ICI then DCN) — one int32 per host
    crosses DCN."""
    is_root = _mesh_index(mesh_axis) == 0
    verdict_int = lax.cond(
        is_root,
        lambda _: tail_fn().astype(jnp.int32),
        lambda _: jnp.int32(0),
        operand=None,
    )
    return lax.psum(verdict_int, _axes(mesh_axis)) > 0


def _sharded_verify(mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
    f_loc, s_part = _local_body(
        pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid
    )
    # gather per-chip partials (1 Fp12 + 1 projective G2 point each);
    # ICI-first on a two-level mesh so only per-host combines cross DCN
    f_all = _gather_fp12_partials(f_loc, mesh_axis)
    axes = _axes(mesh_axis)
    s_all = s_part
    for i, name in enumerate(reversed(axes)):
        s_all = jax.tree.map(lambda x, _n=name: lax.all_gather(x, _n), s_all)
        if i < len(axes) - 1:
            s_all = _g2_sum_tree(s_all)

    def tail():
        s = _g2_sum_tree(s_all)
        s_inf = g2.is_infinity(s)
        s_aff = g2.to_affine(s)
        # e(−g1, S) lane + cross-chip product + final exp
        f_tail = miller_loop_projective(
            (G1_GEN_X, fp.neg(G1_GEN_Y), fp.one(())),
            (s_aff[0], s_aff[1]),
        )
        f_tail = fp12.select(~s_inf, f_tail, fp12.one(()))
        f = fp12.mul(_fp12_product_tree(f_all), f_tail)
        with named_scope("bls/final_exp_batch"):
            return fp12.is_one(final_exponentiation_one(f))

    return _tail_on_root(mesh_axis, tail)


def make_sharded_verifier(mesh: Mesh, axis: str | tuple = "dp"):
    """jit-compiled sharded batch-verify over `mesh`. Batch axis 0 of every
    input must be divisible by the mesh size."""
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
        fn = _shard_map(
            partial(_sharded_verify, axis),
            mesh=mesh,
            in_specs=(spec,) * 8,
            out_specs=P(),
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid)

    return run


# --- grouped (shared-signing-root) tier --------------------------------------


def _grouped_local(
    mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid
):
    """Per-chip slice of the GROUPED batch equation.

    The root axis R is sharded: each chip owns R/n root-rows — their
    pubkey bit-plane MSMs, Horner combines and (A_j, H_j)/(B_j, ψH_j)
    Miller lanes are pure data parallelism. The signature aggregate's
    bit-plane sums span the WHOLE batch: each chip reduces its slice to
    64 partial G2 plane sums, one `all_gather` (64 projective points per
    chip — the only cross-chip traffic besides the final Fp12 partials)
    combines them, and the 64 constant −[2^b]g1 Miller lanes are split
    64/n per chip so the pairing work shards too.

    Returns (local Fp12 pair product, combined u_planes): the combined
    plane sums are replicated post-gather, and the raw twin's subgroup
    check (`planes_in_subgroup`) needs them — the limb path discards
    them because the C tier subgroup-checks on the host."""
    r_loc, lanes = pk_x.shape[0], pk_x.shape[1]
    n_loc = r_loc * lanes
    ndev = _mesh_size(mesh_axis)

    pk = (pk_x, pk_y, fp.one((r_loc, lanes)))
    pk = g1.select(valid, pk, g1.infinity((r_loc, lanes)))
    bits = jnp.concatenate([a_bits, b_bits], axis=-1)

    t_planes = msm.masked_plane_sums(g1, pk, bits)  # (64, r_loc)
    tp = tuple(c.reshape((2, HALF_BITS) + c.shape[1:]) for c in t_planes)
    tp = tuple(jnp.moveaxis(c, 1, 0) for c in tp)
    ab = msm.horner_pow2(g1, tp)  # (2, r_loc)
    a_pt = tuple(c[0] for c in ab)
    b_pt = tuple(c[1] for c in ab)

    # local partial signature plane sums → all_gather → combine
    sig = (
        sig_x.reshape((n_loc,) + sig_x.shape[-2:]),
        sig_y.reshape((n_loc,) + sig_y.shape[-2:]),
        fp2.one((n_loc,)),
    )
    sig = g2.select(valid.reshape(n_loc), sig, g2.infinity((n_loc,)))
    u_part = msm.masked_plane_sums(g2, sig, bits.reshape(n_loc, 2 * HALF_BITS))
    u_planes = _combine_plane_sums(u_part, mesh_axis)  # (64,) over all chips
    u_a = tuple(c[:HALF_BITS] for c in u_planes)
    u_b = g2_psi(tuple(c[HALF_BITS:] for c in u_planes))

    # this chip's slice of the 64 constant lanes (linear index: DCN-major)
    per = (2 * HALF_BITS) // ndev
    start = _mesh_index(mesh_axis) * per
    uq = tuple(
        jnp.concatenate([ca, cb], 0) for ca, cb in zip(u_a, u_b)
    )  # (64,) Q lanes in plane order
    uq_loc = tuple(
        lax.dynamic_slice_in_dim(c, start, per, axis=0) for c in uq
    )
    const_x = jnp.concatenate([NEG_G1_POW2_X, NEG_G1_POW2_X], 0)
    const_y = jnp.concatenate([NEG_G1_POW2_Y, NEG_G1_POW2_Y], 0)
    cx_loc = lax.dynamic_slice_in_dim(const_x, start, per, axis=0)
    cy_loc = lax.dynamic_slice_in_dim(const_y, start, per, axis=0)

    h = (msg_x, msg_y, fp2.one((r_loc,)))
    psi_h = g2_psi(h)
    px = jnp.concatenate([a_pt[0], b_pt[0], cx_loc], 0)
    py = jnp.concatenate([a_pt[1], b_pt[1], cy_loc], 0)
    pz = jnp.concatenate([a_pt[2], b_pt[2], fp.one((per,))], 0)
    qx = jnp.concatenate([h[0], psi_h[0], uq_loc[0]], 0)
    qy = jnp.concatenate([h[1], psi_h[1], uq_loc[1]], 0)
    qz = jnp.concatenate([h[2], psi_h[2], uq_loc[2]], 0)

    lane_ok = ~g1.is_infinity((px, py, pz)) & ~g2.is_infinity((qx, qy, qz))
    fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    fs = fp12.select(lane_ok, fs, fp12.one((2 * r_loc + per,)))
    return _fp12_product_tree(fs), u_planes


def _sharded_grouped_verify(mesh_axis, *args):
    f_loc, _ = _grouped_local(mesh_axis, *args)
    f_all = _gather_fp12_partials(f_loc, mesh_axis)  # (ndev|hosts, 2,3,2,32)

    def tail():
        with named_scope("bls/final_exp_batch"):
            return fp12.is_one(final_exponentiation_one(_fp12_product_tree(f_all)))

    return _tail_on_root(mesh_axis, tail)


def _sharded_grouped_raw_verify(
    mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid
):
    """Raw twin of `_sharded_grouped_verify` (zero-copy wire→mesh ingest):
    each chip decompresses its own (r_loc, lanes, 96) slice of the raw
    signature bytes on device, so the host never touches signature limbs
    and the decode work itself shards with the batch. Semantics mirror
    `grouped_verify_kernel_raw` exactly: lanes that fail to decode are
    masked out of the pairing, any failed VALID lane forces the whole
    verdict False (psum-combined across chips), and the combined
    signature plane sums get the ψ-endomorphism subgroup check — the C
    tier never saw these bytes, so the device must do its own gating."""
    with named_scope("bls/g2_decompress"):
        sig_x, sig_y, dec_ok = _g2_decompress(sig_raw)
    fail_loc = jnp.any(valid & ~dec_ok)
    f_loc, u_planes = _grouped_local(
        mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_x, sig_y,
        a_bits, b_bits, valid & dec_ok,
    )
    f_all = _gather_fp12_partials(f_loc, mesh_axis)
    decode_fail = (
        lax.psum(fail_loc.astype(jnp.int32), _axes(mesh_axis)) > 0
    )

    def tail():
        with named_scope("bls/final_exp_batch"):
            ok = fp12.is_one(
                final_exponentiation_one(_fp12_product_tree(f_all))
            )
        # u_planes is replicated post-gather; running the subgroup check
        # inside the root tail keeps it off the other chips' wall-clock
        return ok & _planes_in_subgroup(u_planes)

    return _tail_on_root(mesh_axis, tail) & ~decode_fail


def make_sharded_grouped_verifier(mesh: Mesh, axis: str | tuple = "dp"):
    """jit-compiled sharded grouped batch-verify over `mesh`. The root
    axis (axis 0 of pk/msg/sig/bits/valid) must be divisible by the mesh
    size, and the mesh size must divide 64 (the constant-lane count)."""
    ndev = mesh.devices.size
    if (2 * HALF_BITS) % ndev != 0:
        # a non-dividing mesh would silently drop constant Miller lanes
        # and reject every valid batch — refuse loudly instead
        raise ValueError(
            f"mesh size {ndev} must divide {2 * HALF_BITS} (constant lanes)"
        )
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid):
        fn = _shard_map(
            partial(_sharded_grouped_verify, axis),
            mesh=mesh,
            in_specs=(spec,) * 9,
            out_specs=P(),
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid)

    return run


def make_sharded_grouped_local_probe(mesh: Mesh, axis: str | tuple = "dp"):
    """INSTRUMENTATION ONLY (tools/mesh_scaling.py): the sharded grouped
    kernel cut after the per-chip local body — MSMs, Horner, the u-plane
    all_gather and per-chip Miller lanes — with the root tail (cross-chip
    Fp12 product + final exp) replaced by a psum checksum. Timing this
    against the full kernel splits a scaling anomaly into "data-parallel
    body" vs "sequential tail" without a profiler on the virtual mesh."""
    ndev = mesh.devices.size
    if (2 * HALF_BITS) % ndev != 0:
        raise ValueError(
            f"mesh size {ndev} must divide {2 * HALF_BITS} (constant lanes)"
        )
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid):
        def probe(*args):
            f_loc, _ = _grouped_local(axis, *args)
            return lax.psum(jnp.sum(f_loc), axis)

        fn = _shard_map(
            probe, mesh=mesh, in_specs=(spec,) * 9, out_specs=P()
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid)

    return run


def make_sharded_grouped_raw_verifier(mesh: Mesh, axis: str | tuple = "dp"):
    """jit-compiled sharded grouped RAW batch-verify over `mesh`:
    signatures enter as (R, L, 96) wire bytes, root-sharded like every
    other input, and decompress on their owning chip. Same divisibility
    contract as `make_sharded_grouped_verifier`."""
    ndev = mesh.devices.size
    if (2 * HALF_BITS) % ndev != 0:
        raise ValueError(
            f"mesh size {ndev} must divide {2 * HALF_BITS} (constant lanes)"
        )
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid):
        fn = _shard_map(
            partial(_sharded_grouped_raw_verify, axis),
            mesh=mesh,
            in_specs=(spec,) * 8,
            out_specs=P(),
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid)

    return run


class ShardedGroupedVerifier:
    """Host wrapper for the sharded grouped kernel: places (R, L) grouped
    arrays root-sharded onto the mesh."""

    def __init__(self, mesh: Mesh, axis: str | tuple = "dp"):
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.devices.size
        self._run = make_sharded_grouped_verifier(mesh, axis)
        self._sharding = NamedSharding(mesh, P(axis))

    def submit(self, g, a_bits, b_bits):
        """Async dispatch: returns the on-device scalar verdict (the
        production pipeline resolves it later, off the dispatch thread)."""
        put = lambda x: jax.device_put(x, self._sharding)
        return self._run(
            put(g.pk_x), put(g.pk_y), put(g.msg_x), put(g.msg_y),
            put(g.sig_x), put(g.sig_y), put(a_bits), put(b_bits),
            put(g.valid),
        )

    def verify_grouped(self, g, a_bits, b_bits) -> bool:
        return bool(self.submit(g, a_bits, b_bits))


class ShardedGroupedRawVerifier:
    """Host wrapper for the sharded grouped RAW kernel: the signature
    tensor is the (R, L, 96) wire-byte scatter straight out of
    `_marshal_grouped(raw=True)` — no host decompression, no limb
    conversion; `device_put` with the row sharding is the only host
    touch before the mesh decodes."""

    def __init__(self, mesh: Mesh, axis: str | tuple = "dp"):
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.devices.size
        self._run = make_sharded_grouped_raw_verifier(mesh, axis)
        self._sharding = NamedSharding(mesh, P(axis))

    def submit(self, g, sig_raw, a_bits, b_bits):
        """Async dispatch: returns the on-device scalar verdict."""
        put = lambda x: jax.device_put(x, self._sharding)
        return self._run(
            put(g.pk_x), put(g.pk_y), put(g.msg_x), put(g.msg_y),
            put(sig_raw), put(a_bits), put(b_bits), put(g.valid),
        )

    def verify_grouped_raw(self, g, sig_raw, a_bits, b_bits) -> bool:
        return bool(self.submit(g, sig_raw, a_bits, b_bits))


# --- pk-grouped (shared-pubkey) tier -----------------------------------------


def _pk_grouped_local(
    mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid
):
    """Per-chip slice of the PK-GROUPED batch equation (the dual kernel:
    rows share a pubkey, messages MSM-combine on the twist).

    The pubkey-row axis R is sharded: each chip owns R/n rows — their
    per-row G2 message MSMs, Horner combines and (pk_k, Σ r_i·H_i) Miller
    lanes are pure data parallelism. The signature aggregate and the 64
    constant −[2^b]g1 lanes follow the same pattern as `_grouped_local`:
    one `all_gather` of 64 partial G2 plane sums, constant lanes split
    64/n per chip."""
    r_loc, lanes = msg_x.shape[0], msg_x.shape[1]
    n_loc = r_loc * lanes
    ndev = _mesh_size(mesh_axis)

    msgs = (msg_x, msg_y, fp2.one((r_loc, lanes)))
    msgs = g2.select(valid, msgs, g2.infinity((r_loc, lanes)))
    bits = jnp.concatenate([a_bits, b_bits], axis=-1)

    m_planes = msm.masked_plane_sums(g2, msgs, bits)  # (64, r_loc)
    tp = tuple(c.reshape((2, HALF_BITS) + c.shape[1:]) for c in m_planes)
    tp = tuple(jnp.moveaxis(c, 1, 0) for c in tp)
    ab = msm.horner_pow2(g2, tp)  # (2, r_loc)
    a_pt = tuple(c[0] for c in ab)
    b_pt = tuple(c[1] for c in ab)
    q_row = g2.add(a_pt, g2_psi(b_pt))  # Σ r_i·H_i per local row

    sig = (
        sig_x.reshape((n_loc,) + sig_x.shape[-2:]),
        sig_y.reshape((n_loc,) + sig_y.shape[-2:]),
        fp2.one((n_loc,)),
    )
    sig = g2.select(valid.reshape(n_loc), sig, g2.infinity((n_loc,)))
    u_part = msm.masked_plane_sums(g2, sig, bits.reshape(n_loc, 2 * HALF_BITS))
    u_planes = _combine_plane_sums(u_part, mesh_axis)
    u_a = tuple(c[:HALF_BITS] for c in u_planes)
    u_b = g2_psi(tuple(c[HALF_BITS:] for c in u_planes))

    per = (2 * HALF_BITS) // ndev
    start = _mesh_index(mesh_axis) * per
    uq = tuple(jnp.concatenate([ca, cb], 0) for ca, cb in zip(u_a, u_b))
    uq_loc = tuple(
        lax.dynamic_slice_in_dim(c, start, per, axis=0) for c in uq
    )
    const_x = jnp.concatenate([NEG_G1_POW2_X, NEG_G1_POW2_X], 0)
    const_y = jnp.concatenate([NEG_G1_POW2_Y, NEG_G1_POW2_Y], 0)
    cx_loc = lax.dynamic_slice_in_dim(const_x, start, per, axis=0)
    cy_loc = lax.dynamic_slice_in_dim(const_y, start, per, axis=0)

    px = jnp.concatenate([pk_x, cx_loc], 0)
    py = jnp.concatenate([pk_y, cy_loc], 0)
    pz = jnp.concatenate([fp.one((r_loc,)), fp.one((per,))], 0)
    qx = jnp.concatenate([q_row[0], uq_loc[0]], 0)
    qy = jnp.concatenate([q_row[1], uq_loc[1]], 0)
    qz = jnp.concatenate([q_row[2], uq_loc[2]], 0)

    lane_ok = ~g1.is_infinity((px, py, pz)) & ~g2.is_infinity((qx, qy, qz))
    fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    fs = fp12.select(lane_ok, fs, fp12.one((r_loc + per,)))
    return _fp12_product_tree(fs), u_planes


def _sharded_pk_grouped_verify(mesh_axis, *args):
    f_loc, _ = _pk_grouped_local(mesh_axis, *args)
    f_all = _gather_fp12_partials(f_loc, mesh_axis)

    def tail():
        with named_scope("bls/final_exp_batch"):
            return fp12.is_one(final_exponentiation_one(_fp12_product_tree(f_all)))

    return _tail_on_root(mesh_axis, tail)


def _sharded_pk_grouped_raw_verify(
    mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid
):
    """Raw twin of `_sharded_pk_grouped_verify`; same decode/subgroup
    gating as `_sharded_grouped_raw_verify` (see there)."""
    with named_scope("bls/g2_decompress"):
        sig_x, sig_y, dec_ok = _g2_decompress(sig_raw)
    fail_loc = jnp.any(valid & ~dec_ok)
    f_loc, u_planes = _pk_grouped_local(
        mesh_axis, pk_x, pk_y, msg_x, msg_y, sig_x, sig_y,
        a_bits, b_bits, valid & dec_ok,
    )
    f_all = _gather_fp12_partials(f_loc, mesh_axis)
    decode_fail = (
        lax.psum(fail_loc.astype(jnp.int32), _axes(mesh_axis)) > 0
    )

    def tail():
        with named_scope("bls/final_exp_batch"):
            ok = fp12.is_one(
                final_exponentiation_one(_fp12_product_tree(f_all))
            )
        return ok & _planes_in_subgroup(u_planes)

    return _tail_on_root(mesh_axis, tail) & ~decode_fail


def make_sharded_pk_grouped_verifier(mesh: Mesh, axis: str | tuple = "dp"):
    """jit-compiled sharded pk-grouped batch-verify over `mesh`. The
    pubkey-row axis must be divisible by the mesh size, and the mesh size
    must divide 64 (the constant-lane count)."""
    ndev = mesh.devices.size
    if (2 * HALF_BITS) % ndev != 0:
        raise ValueError(
            f"mesh size {ndev} must divide {2 * HALF_BITS} (constant lanes)"
        )
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid):
        fn = _shard_map(
            partial(_sharded_pk_grouped_verify, axis),
            mesh=mesh,
            in_specs=(spec,) * 9,
            out_specs=P(),
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, a_bits, b_bits, valid)

    return run


def make_sharded_pk_grouped_raw_verifier(mesh: Mesh, axis: str | tuple = "dp"):
    """jit-compiled sharded pk-grouped RAW batch-verify over `mesh`:
    signatures enter as (R, L, 96) wire bytes and decompress on their
    owning chip. Same divisibility contract as the limb maker."""
    ndev = mesh.devices.size
    if (2 * HALF_BITS) % ndev != 0:
        raise ValueError(
            f"mesh size {ndev} must divide {2 * HALF_BITS} (constant lanes)"
        )
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid):
        fn = _shard_map(
            partial(_sharded_pk_grouped_raw_verify, axis),
            mesh=mesh,
            in_specs=(spec,) * 8,
            out_specs=P(),
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_raw, a_bits, b_bits, valid)

    return run


class ShardedPkGroupedVerifier:
    """Host wrapper for the sharded pk-grouped kernel: places (R,) pubkey
    rows + (R, L) message/signature arrays row-sharded onto the mesh."""

    def __init__(self, mesh: Mesh, axis: str | tuple = "dp"):
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.devices.size
        self._run = make_sharded_pk_grouped_verifier(mesh, axis)
        self._sharding = NamedSharding(mesh, P(axis))

    def submit(self, g, a_bits, b_bits):
        put = lambda x: jax.device_put(x, self._sharding)
        return self._run(
            put(g.pk_x), put(g.pk_y), put(g.msg_x), put(g.msg_y),
            put(g.sig_x), put(g.sig_y), put(a_bits), put(b_bits),
            put(g.valid),
        )

    def verify_pk_grouped(self, g, a_bits, b_bits) -> bool:
        return bool(self.submit(g, a_bits, b_bits))


class ShardedPkGroupedRawVerifier:
    """Host wrapper for the sharded pk-grouped RAW kernel (wire-byte
    signatures; see `ShardedGroupedRawVerifier`)."""

    def __init__(self, mesh: Mesh, axis: str | tuple = "dp"):
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.devices.size
        self._run = make_sharded_pk_grouped_raw_verifier(mesh, axis)
        self._sharding = NamedSharding(mesh, P(axis))

    def submit(self, g, sig_raw, a_bits, b_bits):
        put = lambda x: jax.device_put(x, self._sharding)
        return self._run(
            put(g.pk_x), put(g.pk_y), put(g.msg_x), put(g.msg_y),
            put(sig_raw), put(a_bits), put(b_bits), put(g.valid),
        )

    def verify_pk_grouped_raw(self, g, sig_raw, a_bits, b_bits) -> bool:
        return bool(self.submit(g, sig_raw, a_bits, b_bits))


# --- bisection-verdict tier ---------------------------------------------------


def _bisect_local(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
    """Per-chip leaf terms of the bisection tree: each chip runs the
    scalar ladders and both Miller lanes for its slice of the batch —
    f_i = ML(r_i·pk_i, H_i)·ML(−g1, r_i·sig_i), identity for padding."""
    n_loc = pk_x.shape[0]
    rpk = g1.scalar_mul_bits(r_bits, (pk_x, pk_y))
    rsig = g2.scalar_mul_bits(r_bits, (sig_x, sig_y))
    neg_gy = fp.neg(G1_GEN_Y)
    px = jnp.concatenate(
        [rpk[0], jnp.broadcast_to(G1_GEN_X, (n_loc, N_LIMBS))], 0
    )
    py = jnp.concatenate(
        [rpk[1], jnp.broadcast_to(neg_gy, (n_loc, N_LIMBS))], 0
    )
    pz = jnp.concatenate([rpk[2], fp.one((n_loc,))], 0)
    qx = jnp.concatenate([msg_x, rsig[0]], 0)
    qy = jnp.concatenate([msg_y, rsig[1]], 0)
    qz = jnp.concatenate([fp2.one((n_loc,)), rsig[2]], 0)
    fs = miller_loop_proj_pq((px, py, pz), (qx, qy, qz))
    f = fp12.mul(fs[:n_loc], fs[n_loc:])
    return fp12.select(valid, f, fp12.one((n_loc,)))


def _sharded_bisect_verify(mesh_axis, *args):
    f_loc = _bisect_local(*args)
    # one Fp12 element per leaf per chip; the gathers reconstruct the
    # host's set order (linear chip k owns rows [k·n/ndev, (k+1)·n/ndev)
    # — ICI gathered first, then DCN, matching the DCN-major row
    # sharding; bisect is the audit path, so full leaves crossing DCN on
    # a two-level mesh is acceptable, unlike the hot grouped kernels)
    leaves = f_loc
    for name in reversed(_axes(mesh_axis)):
        leaves = lax.all_gather(leaves, name)
        leaves = leaves.reshape((-1,) + leaves.shape[2:])
    n = leaves.shape[0]

    # the product tree + root final exp are the latency-bound tail; run
    # them on chip 0 only and psum-broadcast every internal level so the
    # host bisection sees the same replicated `levels` the single-device
    # kernel returns (round-4 virtual-mesh lesson: replicated tails burn
    # every "chip"'s shared host core)
    def tree(_):
        levels = []
        g_lvl = leaves
        while g_lvl.shape[0] > 1:
            g_lvl = fp12.mul(g_lvl[0::2], g_lvl[1::2])
            levels.append(g_lvl)
        with named_scope("bls/final_exp_batch"):
            root_ok = fp12.is_one(
                final_exponentiation_one(levels[-1][0])
            ).astype(jnp.int32)
        return root_ok, tuple(levels)

    def idle(_):
        shapes = []
        m = n
        while m > 1:
            m //= 2
            shapes.append(m)
        return jnp.int32(0), tuple(
            jnp.zeros((m,) + leaves.shape[1:], leaves.dtype) for m in shapes
        )

    is_root = _mesh_index(mesh_axis) == 0
    root_int, upper = lax.cond(is_root, tree, idle, operand=None)
    root_int = lax.psum(root_int, _axes(mesh_axis))
    upper = tuple(lax.psum(u, _axes(mesh_axis)) for u in upper)
    return root_int > 0, (leaves,) + upper


def make_sharded_bisect_verifier(mesh: Mesh, axis: str | tuple = "dp"):
    """jit-compiled sharded bisection-tree kernel over `mesh`. The batch
    size must be a power of two (the single-device kernel pads internally;
    here the HOST must pad before sharding so slices stay uniform) and
    divisible by the mesh size. Returns (root_ok, levels) with the same
    level layout as `bisect_tree_kernel`."""
    spec = P(axis)

    @jax.jit
    def run(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid):
        n = pk_x.shape[0]
        if n & (n - 1):
            raise ValueError(f"sharded bisect needs a power-of-two batch, got {n}")
        out_specs = (P(), tuple(P() for _ in range(n.bit_length())))
        fn = _shard_map(
            partial(_sharded_bisect_verify, axis),
            mesh=mesh,
            in_specs=(spec,) * 8,
            out_specs=out_specs,
        )
        return fn(pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid)

    return run


class ShardedBisectVerifier:
    """Host wrapper for the sharded bisection-verdict kernel: places
    padded per-set arrays lane-sharded onto the mesh. Batch size must be
    a power of two divisible by the mesh size."""

    def __init__(self, mesh: Mesh, axis: str | tuple = "dp"):
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.devices.size
        self._run = make_sharded_bisect_verifier(mesh, axis)
        self._sharding = NamedSharding(mesh, P(axis))

    def submit(self, arrs, r_bits):
        put = lambda x: jax.device_put(x, self._sharding)
        root_ok, levels = self._run(
            put(arrs.pk_x), put(arrs.pk_y),
            put(arrs.msg_x), put(arrs.msg_y),
            put(arrs.sig_x), put(arrs.sig_y),
            put(r_bits), put(arrs.valid),
        )
        return root_ok, list(levels)


class ShardedBlsVerifier:
    """Host wrapper: places padded batches onto the mesh and runs the
    sharded kernel. Lane count = bucket per chip × mesh size."""

    def __init__(self, mesh: Mesh, axis: str | tuple = "dp", lanes_per_chip: int = 16):
        self.mesh = mesh
        self.axis = axis
        self.ndev = mesh.devices.size
        self.lanes = lanes_per_chip * self.ndev
        self._run = make_sharded_verifier(mesh, axis)
        self._sharding = NamedSharding(mesh, P(axis))

    def verify_arrays(self, arrs, r_bits):
        put = lambda x: jax.device_put(x, self._sharding)
        return bool(
            self._run(
                put(arrs.pk_x), put(arrs.pk_y),
                put(arrs.msg_x), put(arrs.msg_y),
                put(arrs.sig_x), put(arrs.sig_y),
                put(r_bits), put(arrs.valid),
            )
        )
