"""Mesh-serving policy tests: dispatcher state machine + supervisor wiring.

The round-7 tentpole makes `parallel/mesh.BlsMeshDispatcher` the
production dispatch path whenever >1 chip is visible. Everything here
drives the HOST-side policy — sizing, eviction/re-admission, the
verifier compile cache, fault injection, supervisor retry — with a stub
`verifier_factory` and fake device lists, so no kernel ever compiles
(the sharded-kernel parity itself is covered by the slow tier,
tests/test_sharded_verifier.py)."""

import pytest

from lodestar_tpu.chain.supervisor import SupervisedBlsVerifier
from lodestar_tpu.observability.stages import PipelineMetrics
from lodestar_tpu.parallel.mesh import (
    NOT_SHARDED,
    BlsMeshDispatcher,
    auto_mesh,
    mesh_divisor,
)
from lodestar_tpu.testing import faults
from lodestar_tpu.testing.faults import InjectedChipFault


class _FakeGrouped:
    """Shape-only stand-in for GroupedArrays (rows, lanes)."""

    class _Arr:
        def __init__(self, shape):
            self.shape = shape

    def __init__(self, rows, lanes):
        self.pk_x = self._Arr((rows, lanes))
        self.msg_x = self._Arr((rows, lanes))


class _FakeArrs:
    """Shape-only stand-in for SetArrays (lanes)."""

    class _Arr:
        def __init__(self, shape):
            self.shape = shape

    def __init__(self, lanes):
        self.pk_x = self._Arr((lanes,))


class _StubVerifier:
    def __init__(self, kind, devices, axis):
        self.kind = kind
        self.devices = list(devices)
        self.submits = 0

    def submit(self, *args):
        self.submits += 1
        return True


def _factory_recorder(calls):
    def factory(kind, devices, axis):
        v = _StubVerifier(kind, devices, axis)
        calls.append(v)
        return v

    return factory


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear(reset_counters=True)
    yield
    faults.clear(reset_counters=True)


def _dispatcher(n_devices, observer=None, calls=None):
    calls = calls if calls is not None else []
    return BlsMeshDispatcher(
        [f"dev{i}" for i in range(n_devices)],
        observer=observer or PipelineMetrics(),
        verifier_factory=_factory_recorder(calls),
    )


def test_mesh_divisor_walks_powers_of_two():
    assert [mesh_divisor(n) for n in (1, 2, 3, 5, 7, 8, 64, 100)] == [
        1, 2, 2, 4, 4, 8, 64, 64,
    ]


def test_serving_prefix_and_sizing():
    d = _dispatcher(5)
    assert d.size == 4 and d.enabled
    assert d._serving_chips() == [0, 1, 2, 3]  # chip 4 healthy but idle
    assert _dispatcher(1).enabled is False


def test_dispatch_grouped_routes_and_counts():
    calls = []
    obs = PipelineMetrics()
    d = _dispatcher(4, observer=obs, calls=calls)
    g = _FakeGrouped(8, 64)
    assert d.dispatch_grouped(g, None, None) is True
    assert len(calls) == 1 and calls[0].kind == "grouped"
    assert calls[0].devices == ["dev0", "dev1", "dev2", "dev3"]
    # same shape: the compiled verifier is cached, not rebuilt
    assert d.dispatch_grouped(g, None, None) is True
    assert len(calls) == 1 and calls[0].submits == 2
    snap = obs.mesh_snapshot()
    assert snap["size"] == 4 and snap["evicted"] == 0
    assert snap["chip_dispatches"] == {"0": 2, "1": 2, "2": 2, "3": 2}


class _RunStub(_StubVerifier):
    """A sharded verifier whose jit entry (`_run`) and input sharding
    are what `compile_target` hands to the precompile."""

    def __init__(self, kind, devices, axis):
        super().__init__(kind, devices, axis)
        self._run = lambda *args: True
        self._sharding = ("sharding", kind)

    def submit(self, *args):
        self.submits += 1
        return self._run()


@pytest.mark.parametrize("kind,key,dispatch", [
    ("grouped_raw", (8, 64),
     lambda d: d.dispatch_grouped_raw(_FakeGrouped(8, 64), None, None, None)),
    ("pk_grouped_raw", (8, 64),
     lambda d: d.dispatch_pk_grouped_raw(_FakeGrouped(8, 64), None, None,
                                         None)),
    ("bisect", (8,), lambda d: d.dispatch_bisect(_FakeArrs(8), None)),
])
def test_compile_target_is_the_verifier_its_dispatch_uses(kind, key,
                                                          dispatch):
    """What the precompile compiles is the very verifier (and ledger
    key) the first dispatch of that shape then runs: no second build."""
    calls = []

    def factory(k, devices, axis):
        calls.append(_RunStub(k, devices, axis))
        return calls[-1]

    d = BlsMeshDispatcher(
        [f"dev{i}" for i in range(4)], observer=PipelineMetrics(),
        verifier_factory=factory,
    )
    fn, sharding = d.compile_target(kind, key)
    assert sharding == ("sharding", kind)
    assert fn.__compile_ledger_kernel__ == f"sharded_{kind}"
    assert fn.__compile_ledger_static_key__ == f"{key}@chips0,1,2,3"
    assert dispatch(d)
    assert len(calls) == 1 and calls[0].submits == 1
    assert calls[0]._run is fn


def test_calibration_prices_grouped_shapes_through_the_mesh():
    """A grouped shape the serving mesh takes is priced through its
    sharded dispatch, at the sharded call's own cost; a shape whose rows
    do not split over the mesh, and the per-set buckets, are priced on
    one device, as a dispatch of theirs runs."""
    import time

    import numpy as np

    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    class Sharded(_StubVerifier):
        def submit(self, *args):
            self.submits += 1
            time.sleep(0.01)
            return np.bool_(False)

    calls = []

    def factory(kind, devices, axis):
        calls.append(Sharded(kind, devices, axis))
        return calls[-1]

    mesh = BlsMeshDispatcher([f"dev{i}" for i in range(4)],
                             observer=PipelineMetrics(),
                             verifier_factory=factory)
    v = TpuBlsVerifier(buckets=(4,), grouped_configs=((6, 4), (8, 4)),
                       pk_grouped_configs=((8, 4),), mesh=mesh)
    single = []

    def one_device(name, seconds):
        def call(*args):
            single.append(name)
            time.sleep(seconds)
            return np.bool_(False)
        return call

    v.kernels.verify_batch_raw = one_device("per_set", 0.001)
    v.kernels.verify_grouped_raw = one_device("grouped", 0.05)
    v.kernels.verify_pk_grouped_raw = one_device("pk_grouped", 0.05)
    prices = v.calibrate()
    assert [(c.kind, c.submits) for c in calls] == [
        ("grouped_raw", 1), ("pk_grouped_raw", 1),
    ]
    assert single == ["per_set", "per_set", "grouped"]  # 6 rows: one device
    assert prices[("root_grouped", (8, 4))] < prices[("root_grouped", (6, 4))]
    assert prices[("pk_grouped", (8, 4))] < 0.05


def test_dispatch_grouped_raw_routes_and_counts():
    """ISSUE 15: the zero-copy raw twins route through the same verifier
    cache and chip accounting as the limb kernels, under their own
    (kind, shape) cache keys."""
    calls = []
    obs = PipelineMetrics()
    d = _dispatcher(4, observer=obs, calls=calls)
    g = _FakeGrouped(8, 64)
    assert d.dispatch_grouped_raw(g, None, None, None) is True
    assert len(calls) == 1 and calls[0].kind == "grouped_raw"
    assert calls[0].devices == ["dev0", "dev1", "dev2", "dev3"]
    assert d.dispatch_pk_grouped_raw(g, None, None, None) is True
    assert len(calls) == 2 and calls[1].kind == "pk_grouped_raw"
    # same shapes again: cached verifiers, no new factory calls
    assert d.dispatch_grouped_raw(g, None, None, None) is True
    assert d.dispatch_pk_grouped_raw(g, None, None, None) is True
    assert len(calls) == 2
    assert calls[0].submits == 2 and calls[1].submits == 2
    snap = obs.mesh_snapshot()
    assert snap["chip_dispatches"] == {"0": 4, "1": 4, "2": 4, "3": 4}


def test_dispatch_raw_refuses_indivisible_and_tiny():
    d = _dispatcher(4)
    assert d.dispatch_grouped_raw(
        _FakeGrouped(9, 64), None, None, None
    ) is NOT_SHARDED
    assert d.dispatch_pk_grouped_raw(
        _FakeGrouped(6, 8), None, None, None
    ) is NOT_SHARDED
    assert _dispatcher(1).dispatch_grouped_raw(
        _FakeGrouped(8, 64), None, None, None
    ) is NOT_SHARDED


def test_dispatch_refuses_indivisible_and_tiny_batches():
    d = _dispatcher(4)
    assert d.dispatch_grouped(_FakeGrouped(9, 64), None, None) is NOT_SHARDED
    assert d.dispatch_pk_grouped(_FakeGrouped(6, 8), None, None) is NOT_SHARDED
    # bisect additionally needs the host-padded power-of-two batch
    assert d.dispatch_bisect(_FakeArrs(24), None) is NOT_SHARDED
    assert d.dispatch_bisect(_FakeArrs(16), None) is True
    # a 1-device "mesh" never shards
    assert _dispatcher(1).dispatch_grouped(
        _FakeGrouped(8, 64), None, None
    ) is NOT_SHARDED


def test_eviction_shrinks_readmission_restores():
    obs = PipelineMetrics()
    d = _dispatcher(4, observer=obs)
    assert d.evict(chip=2, reason="deadline") == 2  # 3 healthy -> size 2
    assert d.has_evicted()
    assert d._serving_chips() == [0, 1]
    # no attribution: drop the highest-index healthy chip, keep chip 0
    assert d.evict(reason="failure") == 2
    assert d._serving_chips() == [0, 1]
    assert d.evict() == 1  # 1 healthy: still evictable down to the last
    assert d.evict() is None  # nothing left to evict — caller stops
    snap = d.snapshot()
    assert snap["healthy"] == [0] and len(snap["evicted"]) == 3
    assert d.readmit() == 3
    assert not d.has_evicted() and d.size == 4
    m = obs.mesh_snapshot()
    assert m["evictions"] == {"deadline": 1, "failure": 2}
    assert m["readmissions"] == 3 and m["evicted"] == 0 and m["size"] == 4


def test_verifier_cache_keyed_by_chip_set():
    calls = []
    d = _dispatcher(4, calls=calls)
    g = _FakeGrouped(8, 64)
    d.dispatch_grouped(g, None, None)
    d.evict(chip=3)
    d.dispatch_grouped(g, None, None)  # 2-chip mesh: new compile
    assert [v.devices for v in calls] == [
        ["dev0", "dev1", "dev2", "dev3"], ["dev0", "dev1"],
    ]
    # re-admission returns to the original chip set: the old executable
    # is still cached — no third factory call
    d.readmit()
    d.dispatch_grouped(g, None, None)
    assert len(calls) == 2 and calls[0].submits == 2


def test_chip_fault_is_one_shot_and_attributed():
    d = _dispatcher(4)
    faults.configure("chip:1")
    g = _FakeGrouped(8, 64)
    with pytest.raises(InjectedChipFault) as ei:
        d.dispatch_grouped(g, None, None)
    assert ei.value.chip == 1
    # ONE-SHOT: the plan disarmed itself; the retry after eviction works
    assert d.evict(chip=ei.value.chip, reason="InjectedChipFault") == 2
    assert d.dispatch_grouped(g, None, None) is True
    assert faults.snapshot()["injected"]["chip"] == 1


def test_snapshot_shape():
    d = _dispatcher(3)
    d.dispatch_grouped(_FakeGrouped(8, 64), None, None)
    snap = d.snapshot()
    assert snap["devices_total"] == 3 and snap["size"] == 2
    assert snap["serving"] == [0, 1] and snap["dispatches"] == 1
    assert snap["compiled"] == ["grouped:8x64@2"]


# --- auto_mesh policy --------------------------------------------------------


def test_auto_mesh_env_off(monkeypatch):
    monkeypatch.setenv("LODESTAR_TPU_MESH", "off")
    assert auto_mesh() is None


def test_auto_mesh_cpu_devices_need_force(monkeypatch):
    # tests run with 8 VIRTUAL cpu devices (conftest): auto must refuse —
    # silently meshing a single-host CPU backend is a cold-compile
    # regression for zero parallelism — while force opts in
    monkeypatch.setenv("LODESTAR_TPU_MESH", "auto")
    assert auto_mesh() is None
    monkeypatch.setenv("LODESTAR_TPU_MESH", "force")
    d = auto_mesh(PipelineMetrics())
    assert d is not None and d.enabled and d.size == 8


def test_auto_mesh_that_cannot_be_built_raises(monkeypatch):
    """A multi-device host whose mesh fails to build must not quietly
    serve unsharded (lost chips behind a healthy-looking node)."""
    import lodestar_tpu.parallel.mesh as mesh_mod

    def broken(*a, **kw):
        raise RuntimeError("mesh construction failed")

    monkeypatch.setenv("LODESTAR_TPU_MESH", "force")
    monkeypatch.setattr(mesh_mod, "BlsMeshDispatcher", broken)
    with pytest.raises(RuntimeError, match="mesh construction failed"):
        auto_mesh(PipelineMetrics())


# --- supervisor wiring -------------------------------------------------------


class _FakeMeshDevice:
    """Device facade whose first N dispatches raise an attributed chip
    fault; mesh_* mirrors the dispatcher surface the supervisor uses."""

    def __init__(self, fail_chips=(2,)):
        self._pending = list(fail_chips)
        self.dispatcher = _dispatcher(4)
        self.calls = 0

    def verify_signature_sets(self, sets):
        self.calls += 1
        if self._pending:
            raise InjectedChipFault(self._pending.pop(0))
        return True

    def mesh_evict(self, chip=None, reason="failure"):
        return self.dispatcher.evict(chip=chip, reason=reason)

    def mesh_readmit(self):
        return self.dispatcher.readmit()

    def mesh_has_evicted(self):
        return self.dispatcher.has_evicted()

    def mesh_snapshot(self):
        return self.dispatcher.snapshot()


class _FakeCpu:
    def __init__(self):
        self.calls = 0

    def verify_signature_sets(self, sets):
        self.calls += 1
        return True

    def verify_signature_sets_individual(self, sets):
        self.calls += 1
        return [True] * len(sets)


def _supervised(device, **kw):
    return SupervisedBlsVerifier(
        device,
        _FakeCpu(),
        observer=PipelineMetrics(),
        deadline_s=0,  # inline dispatch: no watchdog thread in unit tests
        canary_thread=False,
        **kw,
    )


def test_supervisor_evicts_sick_chip_and_keeps_serving():
    device = _FakeMeshDevice(fail_chips=(2,))
    sup = _supervised(device)
    assert sup.verify_signature_sets([object()]) is True
    # the chip fault cost an eviction + immediate retry, NOT a CPU
    # fallback, a transient retry, or a breaker failure
    assert device.calls == 2
    assert sup.cpu.calls == 0
    assert sup.breaker_state == "closed"
    assert sup._consecutive_failures == 0
    snap = device.mesh_snapshot()
    assert [e["chip"] for e in snap["evicted"]] == [2]
    assert snap["evicted"][0]["reason"] == "InjectedChipFault"
    assert sup.breaker_snapshot()["mesh"]["size"] == 2


def test_supervisor_eviction_does_not_burn_retry_budget():
    # three successive chip faults: more than the 1-retry transient
    # budget, all absorbed by eviction retries (4 chips -> 1)
    device = _FakeMeshDevice(fail_chips=(0, 1, 2))
    sup = _supervised(device)
    assert sup.verify_signature_sets([object()]) is True
    assert device.calls == 4
    assert sup.cpu.calls == 0


def test_supervisor_falls_back_once_mesh_exhausted():
    # every dispatch raises, chips run out: the ordinary failure policy
    # takes over (transient retry, then CPU oracle) — verdicts stay
    # correct even when the whole mesh is sick
    device = _FakeMeshDevice(fail_chips=(0, 0, 0, 0, 0))
    sup = _supervised(device)
    assert sup.verify_signature_sets([object()]) is True
    assert sup.cpu.calls == 1
    # chip 0 was evicted by attribution, then the unattributed retries
    # dropped 3 and 2 from the top: chip 1 is the lone survivor
    assert device.mesh_snapshot()["healthy"] == [1]


def test_supervisor_probe_readmits_evicted_chips():
    device = _FakeMeshDevice(fail_chips=(1,))
    sup = _supervised(device)
    assert sup.verify_signature_sets([object()]) is True
    assert device.mesh_has_evicted()
    # canary probe with a healthy device: readmit-then-validate
    sup._canary_sets = [object()]
    assert sup.probe() is True
    assert not device.mesh_has_evicted()
    assert device.dispatcher.size == 4


def test_supervisor_probe_reevicts_when_full_mesh_fails():
    device = _FakeMeshDevice(fail_chips=(1,))
    sup = _supervised(device)
    assert sup.verify_signature_sets([object()]) is True

    # the canary dispatch fails WITHOUT chip attribution on the restored
    # full mesh: probe must shrink again rather than leave production on
    # a sick full mesh (and the closed breaker must stay closed)
    def bad_verify(sets):
        device.calls += 1
        raise RuntimeError("sick full mesh")

    device.verify_signature_sets = bad_verify
    sup._canary_sets = [object()]
    assert sup.probe() is False
    assert device.mesh_has_evicted()
    assert sup.breaker_state == "closed"
