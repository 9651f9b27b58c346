"""Test harness setup.

Tests run on a virtual 8-device CPU mesh (no TPU needed): the env vars must be
set before jax initializes its backends. Multi-chip sharding paths are
exercised against this mesh; the driver's `dryrun_multichip` does the same.
The chip itself is reached through `chip_smoke.py`, never through this suite.
"""

import os

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Force CPU: a TPU host's chips belong to one process at a time, and the
# suite must neither claim them nor depend on them.
from lodestar_tpu.utils.jax_env import (  # noqa: E402
    enable_compile_cache,
    force_platform,
)

force_platform("cpu", 8)

import jax  # noqa: E402

# Persistent compilation cache: the pairing/verifier kernels are deep
# (Miller-loop scans + final-exponentiation chains) and take minutes to
# compile on the CPU backend; caching makes repeat suite runs cheap.
# JAX_COMPILATION_CACHE_DIR wins when set; else the repo-local .jax_cache.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)

# AOT executable store (ISSUE 19): hermetic per-run directory unless the
# caller pins one — the suite must neither load a developer's repo-local
# .aot_store (stale executables would mask compile-path regressions) nor
# have tiny-budget prune tests delete its artifacts.
if "LODESTAR_TPU_AOT_STORE" not in os.environ:
    import tempfile

    os.environ["LODESTAR_TPU_AOT_STORE"] = tempfile.mkdtemp(
        prefix="lodestar_aot_test_"
    )

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_mesh():
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devices = np.array(jax.devices("cpu")[:8])
    return Mesh(devices.reshape(8), axis_names=("dp",))
