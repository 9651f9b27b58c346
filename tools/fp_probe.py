"""Compare fp.mul implementations on the current backend: compile time and
steady-state latency of a 100-deep mul chain (the Miller loop's shape of
work). Usage: python tools/fp_probe.py {scan|fused|mxu} BATCH"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()
import jax.numpy as jnp
import numpy as np

mode = sys.argv[1]
batch = int(sys.argv[2])
if mode == "scan":
    os.environ["LODESTAR_TPU_LEGACY_FP"] = "1"
elif mode == "mxu":
    os.environ["LODESTAR_TPU_MXU_MUL"] = "1"
elif mode == "pallas":
    os.environ["LODESTAR_TPU_PALLAS_MUL"] = "1"
elif mode == "mxu2":
    os.environ["LODESTAR_TPU_PALLAS_MXU"] = "1"
elif mode == "padconv":
    os.environ["LODESTAR_TPU_PADCONV_FP"] = "1"

from lodestar_tpu.ops import fp  # noqa: E402

rng = np.random.default_rng(0)
a = jnp.asarray(rng.integers(0, 4096, (batch, 32), dtype=np.int32))
b = jnp.asarray(rng.integers(0, 4096, (batch, 32), dtype=np.int32))


def chain(a, b):
    for _ in range(100):
        a = fp.mul(a, b)
    return a


t0 = time.perf_counter()
f = jax.jit(chain)
r = f(a, b)
r.block_until_ready()
print(f"{mode} b={batch}: compile+first = {time.perf_counter()-t0:.1f}s", flush=True)
t0 = time.perf_counter()
for _ in range(5):
    r = f(a, b)
r.block_until_ready()
print(
    f"{mode} b={batch}: steady 100-mul chain = {(time.perf_counter()-t0)/5*1000:.1f} ms",
    flush=True,
)
