"""The verifier's own spans on the profiler's trace.

Each layer boundary writes a named host span into a JAX profiler trace
started with plain `jax.profiler.start_trace` (as the benchmark starts
it), not through `observability.trace.start_profiling`; counts, lanes and
reasons ride as the span's stats. The queue-wait and CPU-tier
observations land in the pipeline's own families, with no `prom` bundle.
"""

import glob

import jax
import pytest

from lodestar_tpu.chain.bls_verifier import MockBlsVerifier
from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher
from lodestar_tpu.chain.supervisor import SupervisedBlsVerifier
from lodestar_tpu.observability import trace
from lodestar_tpu.observability.compile_ledger import CompileLedger
from lodestar_tpu.observability.spans import Tracer
from lodestar_tpu.observability.stages import STAGES, PipelineMetrics


def traced(tmp_path, fn):
    """Run `fn` inside a profiler trace; returns {span name: [stats]} of
    the host events it wrote."""
    assert not trace.profiling_active()
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(dict(e.stats))
    return out


def _stage_on_trace(p):
    with p.stage("marshal", sets=3):
        pass


def _device_wait_on_trace(p):
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    assert TpuBlsVerifier(observer=p)._resolve(True) is True


@pytest.mark.parametrize("span,stage,run", [
    ("stage/marshal", "marshal", _stage_on_trace),
    ("stage/device_wait", "device_wait", _device_wait_on_trace),
])
def test_a_stage_is_a_span_and_one_histogram_observation(tmp_path, span,
                                                         stage, run):
    p = PipelineMetrics()
    spans = traced(tmp_path, lambda: run(p))
    assert len(spans[span]) == 1
    assert p.stage_snapshot()[stage]["count"] == 1
    if stage == "marshal":
        assert spans[span][0]["sets"] == 3


class _Rejecting(MockBlsVerifier):
    def __init__(self):
        super().__init__(result=False)
        self.observer = None


def test_the_negative_audit_is_a_cpu_tier_span_and_stage(tmp_path):
    p = PipelineMetrics()
    sup = SupervisedBlsVerifier(_Rejecting(), MockBlsVerifier(result=False),
                                observer=p, canary_thread=False)
    try:
        spans = traced(tmp_path,
                       lambda: sup.verify_signature_sets(["a", "b"]))
    finally:
        sup.close()
    assert "cpu_tier" in STAGES
    (meta,) = spans["supervisor/cpu_tier"]
    assert meta == {"reason": "negative_audit", "sets": 2}
    assert p.stage_snapshot()["cpu_tier"]["count"] == 1
    assert p.supervisor_snapshot()["fallbacks"] == {"negative_audit": 1}


def test_a_cpu_tier_call_in_a_lifecycle_trace_is_one_span_and_an_event(
        tmp_path, monkeypatch):
    from lodestar_tpu.observability import spans as lifecycle

    monkeypatch.setattr(lifecycle.tracer, "enabled", True)
    lifecycle.tracer.clear()
    sup = SupervisedBlsVerifier(_Rejecting(), MockBlsVerifier(result=False),
                                observer=PipelineMetrics(),
                                canary_thread=False)

    def audited():
        with lifecycle.tracer.trace("gossip/attestation"):
            sup.verify_signature_sets(["a", "b"])

    try:
        spans = traced(tmp_path, audited)
        (doc,) = lifecycle.tracer.traces()
    finally:
        sup.close()
        lifecycle.tracer.clear()
    assert len(spans["supervisor/cpu_tier"]) == 1
    assert "lifecycle/bls/supervised_batch" in spans
    assert "lifecycle/bls/cpu_fallback" not in spans
    events = [e for s in doc["spans"] for e in s.get("events", ())
              if e["name"] == "bls/cpu_fallback"]
    assert [(e["reason"], e["sets"]) for e in events] == [
        ("negative_audit", 2)]


def test_a_lane_batch_is_a_span_and_each_request_a_lane_wait(tmp_path):
    p = PipelineMetrics()
    d = BlsLaneDispatcher(MockBlsVerifier(), max_sigs=3, max_wait_ms=10_000,
                          workers=1, pending_cap=0, lane_caps={}, pipeline=p)
    try:
        def burst():
            pending = [d.submit(["a1", "a2"], lane="attestation"),
                       d.submit(["g1"], lane="aggregate")]
            assert [r() for r in pending] == [True, True]

        spans = traced(tmp_path, burst)
    finally:
        d.close()
    (meta,) = spans["lane/batch"]
    assert meta == {"lane": "aggregate", "sets": 3, "reason": "size"}
    waits = {lane: n for (lane,), n in p.lane_wait_seconds._totals.items()}
    assert waits == {"attestation": 1, "aggregate": 1}
    assert p.lane_coalesced_sets._totals[()] == 1


def test_a_lifecycle_span_reaches_a_trace_it_did_not_start(tmp_path):
    tracer = Tracer(enabled=True)

    def lifecycle():
        with tracer.trace("gossip/attestation"), tracer.span("chain/bls"):
            pass

    spans = traced(tmp_path, lifecycle)
    assert len(spans["lifecycle/gossip/attestation"]) == 1
    assert len(spans["lifecycle/chain/bls"]) == 1


def test_a_first_dispatch_compile_is_a_span(tmp_path):
    f = CompileLedger().wrap(jax.jit(lambda x: x + 1), "probe_kernel")
    spans = traced(tmp_path, lambda: f(jax.numpy.ones(3)).block_until_ready())
    assert spans["compile/probe_kernel"] == [{"key": "float32[3]"}]


@pytest.mark.parametrize("stored", [False, True])
def test_an_aot_lookup_is_its_own_span_and_a_compile_only_on_a_miss(
        tmp_path, monkeypatch, stored):
    from lodestar_tpu.ops import aot_store

    monkeypatch.setenv("LODESTAR_TPU_AOT_STORE", str(tmp_path / "aot"))
    monkeypatch.delenv("LODESTAR_TPU_AOT_LOAD", raising=False)
    monkeypatch.setenv("LODESTAR_TPU_AOT_EXPORT", "1" if stored else "0")
    aot_store.reset_for_tests()
    x = jax.numpy.arange(8.0)
    try:
        if stored:
            CompileLedger().wrap(jax.jit(lambda v: v * 2 + 1), "probe_aot")(x)
            monkeypatch.setenv("LODESTAR_TPU_AOT_EXPORT", "0")
        f = CompileLedger().wrap(jax.jit(lambda v: v * 2 + 1), "probe_aot")
        spans = traced(tmp_path / "trace",
                       lambda: f(x).block_until_ready())
    finally:
        aot_store.reset_for_tests()
    (lookup,) = spans["compile/aot_lookup"]
    assert lookup == {"kernel": "probe_aot", "key": "float32[8]",
                      **({"hit": 1} if stored else {})}
    assert len(spans.get("compile/probe_aot", ())) == (0 if stored else 1)
