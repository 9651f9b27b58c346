"""Measure full-kernel throughput vs batch size on the real chip."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()


def main():
    from __graft_entry__ import _example_arrays
    from lodestar_tpu.parallel.verifier import batch_verify_kernel

    fn = jax.jit(batch_verify_kernel)
    for batch in (4096, 8192, 16384, 32768):
        args = [jax.device_put(a) for a in _example_arrays(batch, unique=32)]
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        ok = bool(fn(*args))
        t_compile_and_run = time.perf_counter() - t0
        assert ok, f"batch {batch} failed verification"
        t0 = time.perf_counter()
        r = fn(*args)
        r.block_until_ready()
        dt = time.perf_counter() - t0
        print(
            f"batch={batch:6d}  first={t_compile_and_run:8.1f}s  "
            f"steady={dt:7.3f}s  {batch/dt:9.1f} sets/s",
            flush=True,
        )


if __name__ == "__main__":
    main()
