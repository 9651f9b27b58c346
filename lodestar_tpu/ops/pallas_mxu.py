"""Pallas TPU kernel: VMEM-resident Montgomery multiply on the MXU.

The round-4 cut of the lever named since round 2 (BASELINE.md): the
conv-as-matmul design (`ops/fp.conv`) wins microbenchmarks but loses
end-to-end in plain XLA because the matmul cannot fuse its producer —
every convolution materializes the 32x-blowup outer product through HBM.
This kernel runs the SAME proven pipeline per batch tile with every
intermediate in VMEM:

    outer   (T, 1024) int32   a_i * b_j            VPU
    parts   (3T, 1024) bf16   8-bit splits          VPU  (bf16-exact <=255)
    t_cols  = parts @ S       (1024, 64) 0/1        MXU  (f32 accumulate)
    m_cols  = parts(t mod R) @ Toep(N') parts       MXU  (constant matrix)
    u_cols  = parts(m) @ Toep(p) parts              MXU  (constant matrix)
    out     = carry(t_cols + u_cols)[:, 32:]        VPU  (log-depth carry)

versus the word-serial scan path (`fp._mul_scan`): the 32-step REDC scan
and its 32 dynamic-slice updates disappear entirely — reduction becomes
two constant-matrix matmuls — and the only sequential structure left is
three log-depth carry propagations.

Layout: batch on sublanes, limbs on lanes ((T, 32) blocks; the matmul
contraction axis 1024 rides the lane dimension). Carries shift along
lanes via static pad/slice concatenation, which Mosaic lowers to lane
shifts.

Bounds (same argument as `fp._mul_fused`): inputs < 2p with canonical
12-bit limbs, conv columns < 2^29, t+u columns < 2^30 (signed int32 ok),
output < 2p. Matmul exactness: every MXU input is an 8-bit part (<=255,
exact in bf16's 8-bit mantissa); f32 accumulation of <=32 terms of
<=255*255 stays < 2^21 << 2^24.

Oracle: differential tests vs `fp._mul_scan` (tests/test_pallas_mxu.py)
run the kernel in interpret mode on CPU and compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..bls.fields import P as _P_INT
from .limbs import LIMB_BITS, LIMB_MASK, N_LIMBS, P_LIMBS, R_MONT, int_to_limbs
from .pallas_fp import on_tpu

_NPRIME_LIMBS = int_to_limbs((-pow(_P_INT, -1, R_MONT)) % R_MONT)

# default batch-tile height; 8-bit-part working set stays ~3 MB of VMEM
TILE = 256


def _conv_select() -> np.ndarray:
    """(N^2, 2N) 0/1 f32: flattened outer index (i, j) -> column i+j."""
    s = np.zeros((N_LIMBS * N_LIMBS, 2 * N_LIMBS), np.float32)
    for i in range(N_LIMBS):
        for j in range(N_LIMBS):
            s[i * N_LIMBS + j, i + j] = 1.0
    return s


def _toeplitz(vec: np.ndarray, out_cols: int) -> np.ndarray:
    """(N, out_cols) f32 with T[i, k] = vec[k-i]: conv-by-constant as a
    matmul (x @ T)[k] = sum_i x_i vec_{k-i}."""
    t = np.zeros((N_LIMBS, out_cols), np.float32)
    for i in range(N_LIMBS):
        for k in range(out_cols):
            if 0 <= k - i < N_LIMBS:
                t[i, k] = float(vec[k - i])
    return t


def _split8(v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    return v & 0xFF, v >> 8


_S_MAT = _conv_select()
_NP_LO, _NP_HI = _split8(_NPRIME_LIMBS)
_P_LO, _P_HI = _split8(P_LIMBS)
# packed constant matrices: [lo | hi] side by side so one matmul yields
# both part-convolutions (see kernel)
_TN = np.concatenate(
    [_toeplitz(_NP_LO, N_LIMBS), _toeplitz(_NP_HI, N_LIMBS)], axis=1
)  # (32, 64)
_TP = np.concatenate(
    [_toeplitz(_P_LO, 2 * N_LIMBS), _toeplitz(_P_HI, 2 * N_LIMBS)], axis=1
)  # (32, 128)


def _shift_lanes(x: jnp.ndarray, right: int) -> jnp.ndarray:
    """Shift along the last (lane) axis toward higher indices, zero fill."""
    return jnp.pad(x, ((0, 0), (right, 0)))[:, : x.shape[1]]


def _carry_lanes(cols: jnp.ndarray) -> jnp.ndarray:
    """Non-negative-value carry propagation along lanes -> 12-bit digits.

    cols (T, K) int32, columns < 2^30, value non-negative and assumed to
    fit K limbs (out-carry dropped — callers guarantee, same contract as
    `fp.carry_scan`). Three shift-folds bring digits to [0, 2^12]; the
    residual +1 chain resolves with a generate/propagate Kogge–Stone
    prefix (log-depth, lane shifts only)."""
    k = cols.shape[1]

    def fold(x):
        return (x & LIMB_MASK) + _shift_lanes(x >> LIMB_BITS, 1)

    v = fold(fold(fold(cols)))  # digits in [0, 2^12]
    g = (v > LIMB_MASK).astype(jnp.int32)
    p = (v == LIMB_MASK).astype(jnp.int32)
    shift = 1
    while shift < k:
        g_prev = _shift_lanes(g, shift)
        p_prev = _shift_lanes(p, shift)
        g = g | (p & g_prev)
        p = p & p_prev
        shift *= 2
    carry_in = _shift_lanes(g, 1)
    return (v + carry_in) & LIMB_MASK


def _mxu_kernel(a_ref, b_ref, s_ref, tn_ref, tp_ref, out_ref):
    """One (TILE, 32) batch tile of REDC(a*b); see module docstring."""
    a = a_ref[...]
    b = b_ref[...]
    t_rows = a.shape[0]
    n = N_LIMBS

    # outer product (T, 1024): column i*32+j = a_i * b_j
    a_rep = jnp.concatenate(
        [jax.lax.broadcast_in_dim(a[:, i : i + 1], (t_rows, n), (0, 1)) for i in range(n)],
        axis=1,
    )
    b_tile = jnp.concatenate([b] * n, axis=1)
    outer = a_rep * b_tile  # < 2^24

    # 8-bit parts -> one packed (3T, 1024) @ (1024, 64) MXU matmul
    parts = jnp.concatenate(
        [outer & 0xFF, (outer >> 8) & 0xFF, outer >> 16], axis=0
    ).astype(jnp.bfloat16)
    c = jax.lax.dot_general(
        parts,
        s_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    # MOSAIC MISCOMPILE GUARD: `x << k` on a sliced matmul output silently
    # lowers to 0 at tile heights >= 64 (v5e, 2026-07; minimal repro in
    # tests/test_pallas_mxu.py) — recombinations use integer multiplies.
    t_cols = c[:t_rows] + c[t_rows : 2 * t_rows] * 256 + c[2 * t_rows :] * 65536

    t = _carry_lanes(t_cols)  # 64 canonical limbs of a*b

    # m = (t mod R) * N' mod R  — constant-Toeplitz matmul on 8-bit parts
    t_lo = t[:, :n]
    tl = jnp.concatenate([t_lo & 0xFF, t_lo >> 8], axis=0).astype(jnp.bfloat16)
    mm = jax.lax.dot_general(
        tl, tn_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    # mm rows: [t0 | t1] x cols [N'0 | N'1] -> four part convolutions
    m_cols = (
        mm[:t_rows, :n]
        + (mm[:t_rows, n:] + mm[t_rows:, :n]) * 256
        + mm[t_rows:, n:] * 65536
    )
    m = _carry_lanes(m_cols)  # mod R: out-carry dropped

    # u = m * p over 64 columns
    ml = jnp.concatenate([m & 0xFF, m >> 8], axis=0).astype(jnp.bfloat16)
    uu = jax.lax.dot_general(
        ml, tp_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    u_cols = (
        uu[:t_rows, : 2 * n]
        + (uu[:t_rows, 2 * n :] + uu[t_rows:, : 2 * n]) * 256
        + uu[t_rows:, 2 * n :] * 65536
    )

    # (t + m*p) / R: low 32 limbs are ≡ 0 by construction of m
    summed = _carry_lanes(t_cols + u_cols)
    out_ref[...] = summed[:, n:]


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _mxu_tiles(a: jnp.ndarray, b: jnp.ndarray, interpret: bool, tile: int):
    """a, b: (batch_padded, 32) int32, batch_padded % tile == 0."""
    n_tiles = a.shape[0] // tile
    return pl.pallas_call(
        _mxu_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, N_LIMBS), lambda i: (i, 0)),
            pl.BlockSpec((tile, N_LIMBS), lambda i: (i, 0)),
            pl.BlockSpec(_S_MAT.shape, lambda i: (0, 0)),
            pl.BlockSpec(_TN.shape, lambda i: (0, 0)),
            pl.BlockSpec(_TP.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, N_LIMBS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.int32),
        interpret=interpret,
    )(
        a,
        b,
        jnp.asarray(_S_MAT, jnp.bfloat16),
        jnp.asarray(_TN, jnp.bfloat16),
        jnp.asarray(_TP, jnp.bfloat16),
    )


MIN_LANES = 4096  # below this, the ~200 us per-call launch latency loses
# to the scan path (measured v5e round 4: full verifier kernel through the
# Pallas path unconditionally = 867 sets/s vs 1001 scan — the small-batch
# tail sites, e.g. the final-exponentiation chains at unit batch, pay the
# fixed cost thousands of times). Override: LODESTAR_TPU_PALLAS_MIN_LANES.


def _min_lanes() -> int:
    from ..utils.env import env_int

    v = env_int("LODESTAR_TPU_PALLAS_MIN_LANES")
    return v if v else MIN_LANES


def mont_mul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    interpret: bool | None = None,
    tile: int = TILE,
) -> jnp.ndarray:
    """Drop-in for `ops.fp.mul`: framework layout (..., 32), broadcastable
    batch axes, [0, 2p) lazy-reduction contract. Batches smaller than the
    launch-latency break-even fall back to the word-serial scan."""
    if interpret is None:
        interpret = not on_tpu()
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    n_flat = 1
    for d in batch:
        n_flat *= d
    if n_flat < _min_lanes():
        from . import fp as _fp

        return _fp._mul_scan(a, b)
    a = jnp.broadcast_to(a, batch + (N_LIMBS,)).reshape(-1, N_LIMBS)
    b = jnp.broadcast_to(b, batch + (N_LIMBS,)).reshape(-1, N_LIMBS)
    n = a.shape[0]
    t = tile if n >= tile else max(8, 1 << (n - 1).bit_length())
    pad = (-n) % t
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, N_LIMBS), a.dtype)], axis=0)
        b = jnp.concatenate([b, jnp.zeros((pad, N_LIMBS), b.dtype)], axis=0)
    out = _mxu_tiles(a, b, interpret, t)[:n]
    return out.reshape(batch + (N_LIMBS,))
