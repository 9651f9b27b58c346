"""The main-path kernels compiled for a TPU v5e chip, here, without one.

The TPU compiler is installed with JAX and compiles for a described
topology that is not attached, so what Mosaic or XLA:TPU would refuse —
a kernel that does not lower, a program over the chip's memory — fails
these tests at no chip time. Nothing runs: a pass says the programs
build, not that they are right or fast (`chip_smoke.py` does that on the
chip).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the test workers all
import this file.

Fast tier: the building blocks at production widths — the Fp multiply
over the grouped 64x512 lanes, the Fp12 multiply over a 128-set bucket,
G2 decompression of a 64x512 batch of signatures, and the batched final
exponentiation of the 16-lane bisection probe. Slow tier: the whole
verify kernels at the node's shapes (`chain/verifier_stack.BUCKETS` and
`GROUPED_CONFIGS`), and the sharded kernels a 4-chip v5e host compiles
ahead of serving, minutes each.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import pytest

V5E_CHIP_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*shapes).compile()
    seconds = time.monotonic() - t0
    ma = compiled.memory_analysis()
    device_bytes = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
    )
    assert device_bytes < V5E_CHIP_BYTES, device_bytes
    return compiled, seconds


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fp_mul_compiles_for_v5e(one_chip, no_compile_cache):
    from lodestar_tpu.ops import fp

    x = _shape(one_chip, (64 * 512, 32))
    _compile(fp.mul, x, x)


def test_fp12_mul_compiles_for_v5e(one_chip, no_compile_cache):
    from lodestar_tpu.ops import fp12

    x = _shape(one_chip, (128, 2, 3, 2, 32))
    _compile(fp12.mul, x, x)


def test_g2_decompress_compiles_for_v5e(one_chip, no_compile_cache):
    from lodestar_tpu.ops.g2_decompress import decompress

    _compile(decompress, _shape(one_chip, (64, 512, 96), jnp.uint8))


def test_final_exp_batch_compiles_for_v5e(one_chip, no_compile_cache):
    from lodestar_tpu.parallel.verifier import (
        PROBE_LANES,
        final_exp_batch_kernel,
    )

    _compile(
        final_exp_batch_kernel,
        _shape(one_chip, (PROBE_LANES, 2, 3, 2, 32)),
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "kind", ["grouped", "pk_grouped", "per_set", "bisect"]
)
def test_verify_kernels_compile_for_v5e(kind, one_chip, no_compile_cache):
    """Every single-device kernel the node's stack can dispatch on path
    `kind`, at its shapes, as `TpuBlsVerifier.precompile` compiles them
    on the chip."""
    from lodestar_tpu.chain.verifier_stack import BUCKETS, GROUPED_CONFIGS
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    v = TpuBlsVerifier(
        buckets=BUCKETS, grouped_configs=GROUPED_CONFIGS, mesh=None
    )
    jobs = v.kernel_shapes((kind,), sharding=one_chip)
    assert jobs
    for wrapped, shapes in jobs:
        _compile(wrapped.__wrapped__, *shapes)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["grouped", "pk_grouped", "bisect"])
def test_mesh_precompile_targets_compile_for_v5e_2x2(kind, topo,
                                                     no_compile_cache):
    """The sharded kernels the node's stack compiles ahead on a 4-chip
    v5e host (`TpuBlsVerifier.precompile` over the serving mesh), at
    the node's shapes."""
    from lodestar_tpu.chain.verifier_stack import BUCKETS, GROUPED_CONFIGS
    from lodestar_tpu.parallel.mesh import BlsMeshDispatcher
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    v = TpuBlsVerifier(
        buckets=BUCKETS, grouped_configs=GROUPED_CONFIGS,
        mesh=BlsMeshDispatcher(list(topo.devices[:4])),
    )
    jobs = [
        (fn, shapes) for fn, shapes in v.kernel_shapes((kind,))
        if fn.__compile_ledger_kernel__.startswith("sharded_")
    ]
    assert jobs
    for wrapped, shapes in jobs:
        compiled, _ = _compile(wrapped.__wrapped__, *shapes)
        assert "all-gather" in compiled.as_text()


@pytest.mark.slow
@pytest.mark.parametrize("raw", [False, True], ids=["decoded", "raw"])
def test_sharded_grouped_compiles_for_v5e_2x2(raw, topo, no_compile_cache):
    """The sharded grouped kernels (`chip_smoke.py --chips 4` checks
    their parity on the chip) at the full 64x512 over a 4-chip mesh: the
    compiler must accept the collectives and each chip's share."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lodestar_tpu.parallel import sharded

    mesh = Mesh(np.array(topo.devices[:4]).reshape(4), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    r, l = 64, 512
    pk, msg = _shape(rows, (r, l, 32)), _shape(rows, (r, 2, 32))
    bits, valid = _shape(rows, (r, l, 32)), _shape(rows, (r, l), jnp.bool_)
    if raw:
        run = sharded.make_sharded_grouped_raw_verifier(mesh)
        sig = (_shape(rows, (r, l, 96), jnp.uint8),)
    else:
        run = sharded.make_sharded_grouped_verifier(mesh)
        sig = (_shape(rows, (r, l, 2, 32)),) * 2
    compiled, _ = _compile(run, pk, pk, msg, msg, *sig, bits, bits, valid)
    assert "all-gather" in compiled.as_text()
