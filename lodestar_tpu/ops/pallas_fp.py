"""Pallas TPU kernel for the Montgomery field multiply — the innermost
hot op of the pairing pipeline (SURVEY.md §7 hard part #1).

The XLA path (`ops/fp.mul`) materializes the 64-column convolution and
the 32-step REDC scan as separate HLOs with HBM traffic between fusions;
this kernel keeps the entire schoolbook product + Montgomery reduction +
carry propagation in VMEM for a batch tile — one HBM round-trip per tile.

Layout: Pallas tiling wants the last axis = 128 lanes, so the kernel
works on (limbs, batch) blocks — limbs (32/64) on the sublane axis,
batch elements on the lane axis (full 128-lane vregs; the batch-major
layout would use 32/128 lanes). The wrapper transposes from the
framework-wide batch-leading `(..., 32)` layout, pads the batch to a
lane multiple, and restores the layout afterwards.

ROUND-2 REWRITE: the round-1 kernel used `.at[i:i+32].add(...)`
(scatter-add), which Mosaic does not lower (`NotImplementedError:
scatter-add` on real TPU — it only ever ran interpreted). All shifted
accumulations are now static `jnp.pad`s (concatenate lowers fine), so
the kernel compiles for the TC core.

`interpret=True` (automatic off-TPU) runs the same kernel through the
Pallas interpreter, so the differential suite covers it on the CPU
backend; on TPU hardware the compiled kernel is used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .limbs import LIMB_BITS, LIMB_MASK, N_LIMBS, N0, P_LIMBS

LANES = 128


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU. Backend errors propagate:
    a host whose accelerator fails to come up must not be mistaken for a
    CPU host (and silently run Pallas kernels in interpreter mode)."""
    return jax.devices()[0].platform == "tpu"


def _shift_rows(x, down: int, total: int):
    """Pad x (r, L) with `down` zero rows above, to `total` rows."""
    return jnp.pad(x, ((down, total - down - x.shape[0]), (0, 0)))


def _mont_mul_kernel(a_ref, b_ref, p_ref, n0_ref, out_ref):
    """One batch tile: a,b (N, LANES) int32 → REDC(a*b) (N, LANES).

    All intermediates are VMEM values; loops are Python-static, so the
    kernel unrolls into straight-line VPU code with no scatter/gather."""
    a = a_ref[:]
    b = b_ref[:]
    p = p_ref[:]          # (N, LANES) broadcast column of P limbs
    n0 = n0_ref[0, 0]

    n = N_LIMBS
    # schoolbook convolution into 2N uncarried int32 columns: row k of t
    # is Σ_{i+j=k} a_i·b_j — each a-row contributes a shifted copy of
    # a_i * b.
    t = jnp.zeros((2 * n, a.shape[1]), jnp.int32)
    for i in range(n):
        t = t + _shift_rows(a[i, :][None, :] * b, i, 2 * n)

    # word-serial Montgomery reduction: kill one low limb per step.
    # Row updates are built as whole-tensor adds of padded deltas
    # (no scatter): t += shift(m·p, i); then fold row i's residue into
    # row i+1 and zero row i.
    for i in range(n):
        row = t[i, :][None, :]
        m = (row * n0) & LIMB_MASK
        t = t + _shift_rows(m * p, i, 2 * n)
        row = t[i, :][None, :]
        carry = row >> LIMB_BITS
        t = t + _shift_rows(carry, i + 1, 2 * n) - _shift_rows(row, i, 2 * n)

    # carry propagation over the high half → canonical 12-bit limbs.
    # Three shift-folds bring digits to [0, 2^12], then a generate/
    # propagate Kogge-Stone prefix resolves the ±1 chain (log depth —
    # all row shifts are pads, VPU-only).
    hi = t[n:, :]

    def fold(x):
        c = x >> LIMB_BITS
        return (x & LIMB_MASK) + _shift_rows(c[:-1, :], 1, n)

    v = fold(fold(fold(hi)))
    g = (v > LIMB_MASK).astype(jnp.int32)
    pr = (v == LIMB_MASK).astype(jnp.int32)
    shift = 1
    while shift < n:
        g_prev = _shift_rows(g[:-shift, :], shift, n)
        p_prev = _shift_rows(pr[:-shift, :], shift, n)
        g = g | (pr & g_prev)
        pr = pr & p_prev
        shift *= 2
    carry_in = _shift_rows(g[:-1, :], 1, n)
    out_ref[:] = (v + carry_in) & LIMB_MASK


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mont_mul_tiles(a_t: jnp.ndarray, b_t: jnp.ndarray, interpret: bool):
    """a_t, b_t: (N_LIMBS, batch_padded) — batch_padded % LANES == 0."""
    p = jnp.broadcast_to(
        jnp.asarray(P_LIMBS, jnp.int32)[:, None], (N_LIMBS, LANES)
    )
    n0 = jnp.full((1, 1), N0, jnp.int32)
    n_tiles = a_t.shape[1] // LANES
    return pl.pallas_call(
        _mont_mul_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((N_LIMBS, LANES), lambda i: (0, i)),
            pl.BlockSpec((N_LIMBS, LANES), lambda i: (0, i)),
            pl.BlockSpec((N_LIMBS, LANES), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N_LIMBS, LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(a_t.shape, jnp.int32),
        interpret=interpret,
    )(a_t, b_t, p, n0)


def mont_mul(a: jnp.ndarray, b: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in for `ops.fp.mul` backed by the Pallas kernel.

    Accepts the framework layout `(..., N_LIMBS)` with broadcastable batch
    axes; same [0,2p) lazy-reduction contract as fp.mul."""
    if interpret is None:
        interpret = not on_tpu()
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, batch + (N_LIMBS,)).reshape(-1, N_LIMBS)
    b = jnp.broadcast_to(b, batch + (N_LIMBS,)).reshape(-1, N_LIMBS)
    n = a.shape[0]
    pad = (-n) % LANES
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, N_LIMBS), a.dtype)], axis=0)
        b = jnp.concatenate([b, jnp.zeros((pad, N_LIMBS), b.dtype)], axis=0)
    out_t = _mont_mul_tiles(a.T, b.T, interpret)
    out = out_t.T[:n]
    return out.reshape(batch + (N_LIMBS,))
