"""Differential tests for the VMEM-resident MXU Montgomery multiply
(`ops/pallas_mxu.py`) against the word-serial scan oracle (`fp._mul_scan`).

On the CPU backend the kernel runs through the Pallas interpreter
(identical jnp semantics); compiled for a TPU, the Mosaic kernel is
what runs — that path is where the
left-shift-on-sliced-operand miscompile guard matters (see the
MOSAIC MISCOMPILE GUARD note in `_mxu_kernel`: `x << 16` on a sliced
matmul output silently lowered to 0 at tile heights >= 64, v5e 2026-07;
recombinations must stay integer multiplies).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lodestar_tpu.bls.fields import P
from lodestar_tpu.ops import fp
from lodestar_tpu.ops.limbs import N_LIMBS, int_to_limbs, limbs_to_int
from lodestar_tpu.ops.pallas_mxu import mont_mul


def _rand_elems(rng, n, hi):
    vals = [int(rng.integers(0, 2**62)) ** 7 % hi for _ in range(n)]
    return vals, jnp.asarray(np.stack([int_to_limbs(v) for v in vals]))


@pytest.mark.parametrize("n", [1, 8, 37, 256, 300])
def test_mont_mul_matches_scan(n):
    rng = np.random.default_rng(n)
    _, a = _rand_elems(rng, n, 2 * P)
    _, b = _rand_elems(rng, n, 2 * P)
    ref = np.asarray(fp._mul_scan(a, b))
    got = np.asarray(mont_mul(a, b))
    assert (ref == got).all()


def test_mont_mul_edge_values():
    # 0, 1, p-1, p, 2p-1 in all pairings: the [0, 2p) contract's corners
    vals = [0, 1, P - 1, P, 2 * P - 1]
    a = jnp.asarray(np.stack([int_to_limbs(x) for x in vals for _ in vals]))
    b = jnp.asarray(np.stack([int_to_limbs(y) for _ in vals for y in vals]))
    ref = np.asarray(fp._mul_scan(a, b))
    got = np.asarray(mont_mul(a, b))
    assert (ref == got).all()
    # outputs respect the lazy-reduction bound and the ring semantics
    R_inv = pow(1 << 384, -1, P)
    for i, (x, y) in enumerate([(x, y) for x in vals for y in vals]):
        out = limbs_to_int(np.asarray(got[i]))
        assert out < 2 * P
        assert out % P == (x * y * R_inv) % P


def test_mont_mul_broadcasting_and_stacks():
    """The tower stacks muls on leading axes (fp2.mul: (3, batch, 32));
    the wrapper must flatten/broadcast identically to fp.mul."""
    rng = np.random.default_rng(7)
    _, a = _rand_elems(rng, 6, 2 * P)
    _, b = _rand_elems(rng, 6, 2 * P)
    a3 = a.reshape(3, 2, N_LIMBS)
    b3 = b.reshape(3, 2, N_LIMBS)
    ref = np.asarray(fp._mul_scan(a3, b3))
    got = np.asarray(mont_mul(a3, b3))
    assert ref.shape == got.shape == (3, 2, N_LIMBS)
    assert (ref == got).all()
    # broadcast one operand over the stack axis
    ref_b = np.asarray(fp._mul_scan(a3, b3[0]))
    got_b = np.asarray(mont_mul(a3, b3[0]))
    assert (ref_b == got_b).all()


def test_mont_mul_chain_against_oracle():
    """A short dependency chain (the Miller loop's shape of reuse):
    errors that cancel on one multiply would compound here."""
    rng = np.random.default_rng(11)
    vals, a = _rand_elems(rng, 16, 2 * P)
    bvals, b = _rand_elems(rng, 16, 2 * P)
    x = a
    for _ in range(5):
        x = mont_mul(x, b)
    R_inv = pow(1 << 384, -1, P)
    got = np.asarray(x)
    for i in range(16):
        exp = vals[i]
        for _ in range(5):
            exp = exp * bvals[i] * R_inv % P
        assert limbs_to_int(got[i]) % P == exp
