"""Profile the BLS batch-verify kernel piecewise on the real chip.

Times each stage of batch_verify_kernel at the bench shape so the next
optimisation target is measured, not guessed. Run:  python tools/profile_kernel.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()

BATCH = int(os.environ.get("PROFILE_BATCH", "4096"))
REPS = int(os.environ.get("PROFILE_REPS", "3"))


def timeit(name, fn, *args):
    fn_j = jax.jit(fn)
    r = fn_j(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(REPS):
        r = fn_j(*args)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / REPS
    print(f"{name:40s} {dt*1e3:10.2f} ms")
    return dt


def main():
    from lodestar_tpu.ops import fp, fp2, fp12
    from lodestar_tpu.ops.pairing import (
        final_exponentiation,
        miller_loop_projective,
    )
    from lodestar_tpu.ops.points import g1, g2
    from lodestar_tpu.parallel.verifier import N_LIMBS
    from __graft_entry__ import _example_arrays

    print(f"batch={BATCH} reps={REPS} device={jax.devices()[0]}")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 1 << 12, (BATCH, N_LIMBS), dtype=np.int32))
    b = jnp.asarray(rng.integers(0, 1 << 12, (BATCH, N_LIMBS), dtype=np.int32))
    a2 = jnp.stack([a, b], axis=-2)
    b2 = jnp.stack([b, a], axis=-2)

    def chain_mul(a, b):
        # 16 chained muls: amortizes dispatch, defeats CSE via data dep
        x = a
        for _ in range(16):
            x = fp.mul(x, b)
        return x

    dt = timeit("fp.mul x16 chained", chain_mul, a, b)
    print(f"  -> per fp.mul: {dt/16*1e3:.3f} ms")

    def chain_mul2(a, b):
        x = a
        for _ in range(16):
            x = fp2.mul(x, b)
        return x

    dt = timeit("fp2.mul x16 chained", chain_mul2, a2, b2)
    print(f"  -> per fp2.mul: {dt/16*1e3:.3f} ms")

    args = [jax.device_put(x) for x in _example_arrays(BATCH)]
    jax.block_until_ready(args)
    (pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid) = args

    timeit("g1.scalar_mul_bits (64-bit)", lambda r, x, y: g1.scalar_mul_bits(r, (x, y)), r_bits, pk_x, pk_y)
    timeit("g2.scalar_mul_bits (64-bit)", lambda r, x, y: g2.scalar_mul_bits(r, (x, y)), r_bits, sig_x, sig_y)

    def ml(px, py, qx, qy):
        return miller_loop_projective((px, py, fp.one((BATCH,))), (qx, qy))

    dt_ml = timeit("miller_loop (batch lanes)", ml, pk_x, pk_y, msg_x, msg_y)

    f = ml(pk_x, pk_y, msg_x, msg_y)
    f = jax.block_until_ready(jax.jit(lambda x: x)(f))
    timeit("fp12.product_tree", fp12.product_tree, f)
    timeit("final_exponentiation (1 lane)", final_exponentiation, f[:1])

    def sq_chain(f):
        x = f
        for _ in range(4):
            x = fp12.square(x)
        return x

    dt = timeit("fp12.square x4 chained", sq_chain, f)
    print(f"  -> per fp12.square: {dt/4*1e3:.3f} ms")


if __name__ == "__main__":
    main()
