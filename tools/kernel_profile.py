"""Component-level profile of batch_verify_kernel at a given batch size.

Times each stage as its own jitted kernel (device-resident inputs):
  scalar muls (G1, G2) · G2 sum tree · Miller loop · Fp12 product tree ·
  final exponentiation. The sum of parts exceeds the fused kernel's time
  (XLA overlaps stages), but the RATIOS say where the next optimization
  dollar goes. Usage: python tools/kernel_profile.py [BATCH]
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()
import jax.numpy as jnp

from __graft_entry__ import _example_arrays
from lodestar_tpu.ops import fp, fp12
from lodestar_tpu.ops.pairing import final_exponentiation, miller_loop_projective
from lodestar_tpu.ops.points import G1_GEN_X, G1_GEN_Y, g1, g2
from lodestar_tpu.parallel import verifier as V

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
pk_x, pk_y, msg_x, msg_y, sig_x, sig_y, r_bits, valid = [
    jax.device_put(a) for a in _example_arrays(B)
]
jax.block_until_ready([pk_x, r_bits])


def bench(name, fn, *args, reps=3):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    print(f"{name:28s} {dt*1000:9.1f} ms   (compile+1 {compile_s:.1f}s)", flush=True)
    return out


f_g1 = jax.jit(lambda b, x, y: g1.scalar_mul_bits(b, (x, y)))
f_g2 = jax.jit(lambda b, x, y: g2.scalar_mul_bits(b, (x, y)))
rpk = bench("g1 scalar mul (r_i*pk_i)", f_g1, r_bits, pk_x, pk_y)
rsig = bench("g2 scalar mul (r_i*sig_i)", f_g2, r_bits, sig_x, sig_y)

f_tree = jax.jit(lambda x, y, z: V._g2_sum_tree((x, y, z)))
s_pt = bench("g2 sum tree", f_tree, *rsig)

f_aff = jax.jit(lambda x, y, z: g2.to_affine((x, y, z)))
s_aff = bench("g2 to_affine (1 fp2 inv)", f_aff, *s_pt)


def miller_all(rx, ry, rz, mx, my, sx, sy):
    xs = jnp.concatenate([rx, G1_GEN_X[None]], 0)
    ys = jnp.concatenate([ry, fp.neg(G1_GEN_Y)[None]], 0)
    zs = jnp.concatenate([rz, fp.one((1,))], 0)
    qx = jnp.concatenate([mx, sx[None]], 0)
    qy = jnp.concatenate([my, sy[None]], 0)
    return miller_loop_projective((xs, ys, zs), (qx, qy))


f_miller = jax.jit(miller_all)
fs = bench(
    "miller loops (B+1)", f_miller, rpk[0], rpk[1], rpk[2],
    msg_x, msg_y, s_aff[0], s_aff[1],
)

f_prod = jax.jit(fp12.product_tree)
prod = bench("fp12 product tree", f_prod, fs)

f_fe = jax.jit(lambda f: fp12.is_one(final_exponentiation(f[None]))[0])
bench("final exponentiation (x1)", f_fe, prod)
