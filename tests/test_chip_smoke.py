"""`chip_smoke.py` and the verifier stack it shares with the node.

The smoke itself runs only on a TPU; here its contract off the chip (no
TPU → non-zero exit, no result line; the same for `bench.py`), its
traffic generators at a tiny size against the CPU tier, its failure
checks, and `chain/verifier_stack.build_verifier_stack`'s wiring.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_without_accelerator(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120,
    )


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_fails_without_an_accelerator_and_prints_no_result(script):
    proc = _run_without_accelerator(script)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert not proc.stdout.strip().startswith("{")


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, REPO)
    import chip_smoke

    return chip_smoke


@pytest.fixture(scope="module")
def cpu():
    from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier

    return CpuBlsVerifier()


def test_traffic_is_valid_and_tamper_pinpoints_one_set(smoke, cpu):
    keys = smoke.Keys(seed=5, n=16)
    gossip = smoke.make_gossip(keys, 5, roots=2, lanes=8, signers=4)
    block = smoke.make_block(keys, 5, aggregates=2, committee=4, sync=8)
    assert len(gossip.sets) == 16 and len(gossip.distinct) == 8
    assert len({s.message for s in gossip.sets}) == 2
    assert len(block.sets) == 2 + 2 + 1
    assert len({s.message for s in block.sets}) == len(block.sets)
    stack = type("Stack", (), {"cpu": cpu})()
    assert all(smoke.cpu_reference(stack, gossip))
    assert all(smoke.cpu_reference(stack, block))
    for traffic in (gossip, block):
        bad, k = smoke.tamper(traffic, 9)
        ref = smoke.cpu_reference(stack, bad)
        assert [i for i, v in enumerate(ref) if not v] == [k]


def test_counter_checks_flag_every_failure_mode(smoke):
    clean = {
        "breaker": "closed", "breaker_state_gauge": 0,
        "fallbacks": {"negative_audit": 3}, "retries": 0,
        "deadline_exceeded": 0, "both_tiers_failed": 0,
        "verdict_mismatches": 0, "waiter_timeouts": 0,
        "decompress_fallbacks": 0,
    }
    assert smoke.check_counters(clean) == []
    for key, bad in (
        ("fallbacks", {"negative_audit": 3, "exception": 1}),
        ("retries", 1), ("deadline_exceeded", 1), ("both_tiers_failed", 1),
        ("verdict_mismatches", 1), ("waiter_timeouts", 1),
        ("decompress_fallbacks", 1), ("breaker_state_gauge", 2),
        ("breaker", "open"),
    ):
        assert smoke.check_counters(dict(clean, **{key: bad})), key


def test_stack_wires_the_sizing_through_every_layer(monkeypatch):
    from lodestar_tpu.chain import verifier_stack
    from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier, DeviceBlsVerifier
    from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher
    from lodestar_tpu.chain.supervisor import SupervisedBlsVerifier
    from lodestar_tpu.observability.stages import PipelineMetrics
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    # the stack compiles every kernel its device tier can dispatch
    # before it is returned; here only the list of what it would compile
    compiled = []

    def precompile(self):
        compiled.extend(
            fn.__compile_ledger_kernel__ for fn, _ in self.kernel_shapes()
        )
        return list(compiled)

    monkeypatch.setattr(TpuBlsVerifier, "precompile", precompile)
    stack = verifier_stack.build_verifier_stack(PipelineMetrics())
    try:
        assert isinstance(stack.dispatcher, BlsLaneDispatcher)
        assert isinstance(stack.supervisor, SupervisedBlsVerifier)
        assert isinstance(stack.device, DeviceBlsVerifier)
        assert isinstance(stack.cpu, CpuBlsVerifier)
        assert stack.dispatcher.verifier is stack.supervisor
        assert stack.supervisor.device is stack.device
        assert stack.supervisor.cpu is stack.cpu
        # the dispatcher's defaults coalesce and queue one grouped batch
        rows, lanes = verifier_stack.GROUPED_CONFIGS[-1]
        assert stack.dispatcher.max_coalesce == rows * lanes
        assert stack.dispatcher.lane_caps["attestation"] >= rows * lanes
        assert stack.dispatcher.pending_cap >= rows * lanes
        assert stack.device.max_sets_per_job == rows * lanes
        assert stack.device.max_sets_per_verdict_job == verifier_stack.BUCKETS[-1]
        kernels = stack.device._inner.kernels
        assert kernels.buckets == verifier_stack.BUCKETS
        assert kernels.grouped_configs == verifier_stack.GROUPED_CONFIGS
        # compiled ahead: the raw (device-decompress) kernels, one per
        # config and bucket, plus the bisection probe
        assert stack.compiled == compiled == [
            "grouped_raw", "pk_grouped_raw", "batch_raw", "bisect_tree",
            "bisect_probe",
        ]
    finally:
        stack.close()


def test_precompile_targets_the_mesh_twins_where_the_mesh_serves():
    """On a serving mesh, the grouped, pk-grouped and bisection shapes
    compile as the sharded twins that dispatch routes them to, with
    their inputs on the mesh; the per-set batch and the probe stay
    single-device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lodestar_tpu.parallel.mesh import BlsMeshDispatcher
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    built = []

    class Twin:  # a sharded verifier without its kernel
        def __init__(self, kind, devices, axis):
            self._run = lambda *a: None
            self._sharding = NamedSharding(
                Mesh(np.array(devices), (axis,)), P(axis)
            )
            built.append(kind)

    mesh = BlsMeshDispatcher(jax.devices()[:4], verifier_factory=Twin)
    v = TpuBlsVerifier(buckets=(12, 16), grouped_configs=((6, 4), (8, 4)),
                       pk_grouped_configs=((4, 4),), mesh=mesh)
    jobs = v.kernel_shapes()
    names = [fn.__compile_ledger_kernel__ for fn, _ in jobs]
    assert names == [
        "grouped_raw", "sharded_grouped_raw",  # 6 rows do not split 4 ways
        "sharded_pk_grouped_raw",
        "batch_raw", "bisect_tree",  # 12 lanes: not a power of two
        "batch_raw", "sharded_bisect",
        "bisect_probe",
    ]
    assert built == ["grouped_raw", "pk_grouped_raw", "bisect"]
    for fn, shapes in jobs:
        on_mesh = fn.__compile_ledger_kernel__.startswith("sharded_")
        assert all((s.sharding is not None) == on_mesh for s in shapes)
    # the ledger key is the one the mesh dispatch records under
    assert jobs[1][0].__compile_ledger_static_key__ == "(8, 4)@chips0,1,2,3"


@pytest.mark.parametrize("buckets,grouped,job,verdict_job", [
    ((4, 16, 64, 128), ((16, 8), (64, 64)), 64 * 64, 128),
    ((1024,), ((64, 512),), 64 * 512, 1024),
    ((256,), ((4, 8),), 256, 256),
])
def test_device_tier_jobs_fill_its_largest_kernel(buckets, grouped, job,
                                                  verdict_job):
    """A batch job is as large as the largest grouped kernel (or bucket)
    holds; a per-set verdict job as large as the largest bucket."""
    from lodestar_tpu.chain.bls_verifier import DeviceBlsVerifier

    dev = DeviceBlsVerifier(buckets=buckets, grouped_configs=grouped)
    assert dev.max_sets_per_job == job
    assert dev.max_sets_per_verdict_job == verdict_job


def test_smoke_fails_when_a_kernel_compiles_while_serving(smoke):
    """A first dispatch that compiles after the stack was built is the
    failure the stack build exists to rule out."""
    import jax
    import jax.numpy as jnp

    from lodestar_tpu.observability.compile_ledger import ledger

    d = object.__new__(smoke.Driver)
    d.problems = []
    d.n_compiles = smoke.log_compiles()
    d.check_no_compiles()
    assert d.problems == []
    ledger().wrap(jax.jit(lambda x: x - 1), "t_serving_compile")(
        jnp.arange(3.0)
    )
    d.check_no_compiles()
    assert len(d.problems) == 1 and "serving path" in d.problems[0]
