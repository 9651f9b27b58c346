"""Measure the grouped batch-verify kernel on device.

Shapes: (R roots × L lanes) gossip shape — the bench's 64-unique-root
batch (BASELINE config #2). Prints compile time and steady-state sets/s
per config. Run on the TPU (default env) or CPU (JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import os
import sys
import time


sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()


def example_grouped(rows: int, lanes: int):
    """Valid grouped arrays: one signer per root, tiled across lanes."""
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.bls.hash_to_curve import hash_to_g2
    from lodestar_tpu.ops.io_host import g1_affine_to_limbs, g2_affine_to_limbs
    from lodestar_tpu.parallel.verifier import GroupedArrays, _rand_pairs

    g = GroupedArrays(rows, lanes)
    for j in range(rows):
        sk = bls.interop_secret_key(j)
        msg = bytes([j]) * 32
        pkx, pky, _ = g1_affine_to_limbs(sk.to_public_key().point)
        h = hash_to_g2(msg)
        g.msg_x[j], g.msg_y[j], _ = g2_affine_to_limbs(h)
        sx, sy, _ = g2_affine_to_limbs(sk.sign(msg).point)
        g.pk_x[j, :] = pkx
        g.pk_y[j, :] = pky
        g.sig_x[j, :] = sx
        g.sig_y[j, :] = sy
    g.valid[:] = True
    g.n = rows * lanes
    a_bits, b_bits = _rand_pairs((rows, lanes))
    return g, a_bits, b_bits


def probe(rows: int, lanes: int, reps: int = 3):
    from lodestar_tpu.parallel.verifier import grouped_verify_kernel

    g, a_bits, b_bits = example_grouped(rows, lanes)
    args = [
        jax.device_put(a)
        for a in (
            g.pk_x, g.pk_y, g.msg_x, g.msg_y, g.sig_x, g.sig_y,
            a_bits, b_bits, g.valid,
        )
    ]
    jax.block_until_ready(args)
    fn = jax.jit(grouped_verify_kernel)
    t0 = time.perf_counter()
    ok = bool(fn(*args))
    compile_s = time.perf_counter() - t0
    print(f"({rows},{lanes}) compile+first: {compile_s:.1f}s ok={ok}", flush=True)
    assert ok, "valid grouped batch rejected"
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    r.block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    n = rows * lanes
    print(
        f"({rows},{lanes}) steady: {dt*1e3:.0f} ms -> {n/dt:.1f} sets/s",
        flush=True,
    )
    return n / dt


if __name__ == "__main__":
    shapes = sys.argv[1:] or ["64x64"]
    for s in shapes:
        r, l = (int(v) for v in s.split("x"))
        probe(r, l)
