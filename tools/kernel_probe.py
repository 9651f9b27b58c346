"""Time batch_verify_kernel compile + steady state for one batch size.
Usage: python tools/kernel_probe.py {default|scan|mxu} BATCH [REPS]"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

mode = sys.argv[1]
batch = int(sys.argv[2])
reps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
if mode == "scan":
    os.environ["LODESTAR_TPU_LEGACY_FP"] = "1"
elif mode == "mxu":
    os.environ["LODESTAR_TPU_MXU_MUL"] = "1"
elif mode == "mxu2":
    os.environ["LODESTAR_TPU_PALLAS_MXU"] = "1"

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()

from __graft_entry__ import _example_arrays  # noqa: E402
from lodestar_tpu.parallel.verifier import batch_verify_kernel  # noqa: E402

args = [jax.device_put(a) for a in _example_arrays(batch)]
jax.block_until_ready(args)
fn = jax.jit(batch_verify_kernel)

t0 = time.perf_counter()
ok = bool(fn(*args))
print(
    f"{mode} b={batch}: compile+first = {time.perf_counter()-t0:.1f}s ok={ok}",
    flush=True,
)
assert ok
t0 = time.perf_counter()
for _ in range(reps):
    r = fn(*args)
r.block_until_ready()
dt = (time.perf_counter() - t0) / reps
print(f"{mode} b={batch}: steady = {dt:.3f}s  {batch/dt:.1f} sets/s", flush=True)
