"""Cross-check windowed vs bits ladders ON DEVICE (and time honestly)."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()
import jax.numpy as jnp
import numpy as np

from lodestar_tpu.bls import curve as oc
from lodestar_tpu.ops.io_host import g2_affine_to_limbs
from lodestar_tpu.ops.points import g2
from lodestar_tpu.ops import fp

B = 512
rng = np.random.default_rng(0)
bits_np = rng.integers(0, 2, (B, 64), dtype=np.int32)
bits = jnp.asarray(bits_np)
g2x, g2y, _ = g2_affine_to_limbs(oc.PointG2.generator())
q = (jnp.broadcast_to(g2x, (B, 2, 32)), jnp.broadcast_to(g2y, (B, 2, 32)))

f_bits = jax.jit(g2.scalar_mul_bits)
f_win = jax.jit(g2.scalar_mul_windowed)
r1 = f_bits(bits, q)
r2 = f_win(bits, q)
jax.block_until_ready((r1, r2))

# compare affine forms (projective reps differ)
a1 = g2.to_affine(r1)
a2 = g2.to_affine(r2)
eq = jnp.all(fp.eq(a1[0], a2[0]) & fp.eq(a1[1], a2[1]))
print("windowed == bits on device:", bool(jnp.all(eq)))

for name, f in (("bits", f_bits), ("windowed", f_win)):
    # fresh input each rep to defeat any caching
    t0 = time.perf_counter()
    outs = []
    for i in range(3):
        b = jnp.asarray(np.roll(bits_np, i, axis=0))
        outs.append(f(b, q))
    jax.block_until_ready(outs)
    print(f"g2 {name} B={B}: {(time.perf_counter()-t0)/3*1000:.1f} ms/rep", flush=True)
