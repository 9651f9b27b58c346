"""Compare scalar-ladder variants on the current backend (compile + steady)."""
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
from lodestar_tpu.ops.io_host import g1_affine_to_limbs, g2_affine_to_limbs
from lodestar_tpu.ops.points import g1, g2

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
rng = np.random.default_rng(0)
bits = jnp.asarray(rng.integers(0, 2, (B, 64), dtype=np.int32))

g1x, g1y, _ = g1_affine_to_limbs(oc.PointG1.generator())
g2x, g2y, _ = g2_affine_to_limbs(oc.PointG2.generator())
cases = [
    ("g1 bits", g1.scalar_mul_bits, (jnp.broadcast_to(g1x, (B, 32)), jnp.broadcast_to(g1y, (B, 32)))),
    ("g1 windowed", g1.scalar_mul_windowed, (jnp.broadcast_to(g1x, (B, 32)), jnp.broadcast_to(g1y, (B, 32)))),
    ("g2 bits", g2.scalar_mul_bits, (jnp.broadcast_to(g2x, (B, 2, 32)), jnp.broadcast_to(g2y, (B, 2, 32)))),
    ("g2 windowed", g2.scalar_mul_windowed, (jnp.broadcast_to(g2x, (B, 2, 32)), jnp.broadcast_to(g2y, (B, 2, 32)))),
]
for name, fn, q in cases:
    f = jax.jit(fn)
    t0 = time.perf_counter()
    r = f(bits, q)
    jax.block_until_ready(r)
    t_c = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(3):
        r = f(bits, q)
    jax.block_until_ready(r)
    print(
        f"{name} B={B}: compile+1={t_c:.1f}s steady={(time.perf_counter()-t0)/3*1000:.0f} ms",
        flush=True,
    )
