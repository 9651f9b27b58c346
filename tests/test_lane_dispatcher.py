"""BlsLaneDispatcher policy tests (ISSUE 15 tentpole C): priority
lanes, admission control / load-shedding, eviction order, continuous-
batching overlap, and shutdown semantics.

Everything here drives the HOST-side dispatcher state machine with mock
verifiers (no crypto, no jax) so it stays in the fast tier. The key
regression (satellite 6): a shed waiter must get its typed
`BlsShedError` PROMPTLY — never ride out the 300 s waiter timeout.
"""

import threading
import time

import pytest

from lodestar_tpu.chain.bls_verifier import BlsShedError, MockBlsVerifier
from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher, DEFAULT_LANE, LANES
from lodestar_tpu.observability.stages import PipelineMetrics

# Far above any prompt-shed assertion: if a test waits anywhere near
# this, the dispatcher hung a waiter instead of rejecting it.
WAITER_TIMEOUT_S = 60.0


class _GateVerifier(MockBlsVerifier):
    """Mock whose verify blocks until `gate` is set — holds workers
    in-flight so queues accumulate deterministically."""

    def __init__(self):
        super().__init__(result=True)
        self.gate = threading.Event()
        self.started = threading.Event()
        self._lock = threading.Lock()
        self.calls: list[list] = []

    def verify_signature_sets(self, sets) -> bool:
        with self._lock:
            self.calls.append(list(sets))
        self.started.set()
        self.gate.wait(10.0)
        return super().verify_signature_sets(sets)


def _dispatcher(verifier=None, **kw):
    kw.setdefault("max_sigs", 32)
    kw.setdefault("max_wait_ms", 10_000)  # timer never fires in-test
    kw.setdefault("workers", 1)
    kw.setdefault("pending_cap", 0)  # off unless a test opts in
    kw.setdefault("lane_caps", {})
    kw.setdefault("waiter_timeout_s", WAITER_TIMEOUT_S)
    kw.setdefault("pipeline", PipelineMetrics())
    return BlsLaneDispatcher(verifier or MockBlsVerifier(), **kw)


def _submit_bg(d, sets, lane):
    """Submit from a background thread; returns (thread, outcome list)."""
    out: list = []

    def run():
        try:
            out.append(("ok", d.verify_signature_sets(sets, lane=lane)))
        except BlsShedError as e:
            out.append(("shed", e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, out


def _wait_queued(d, n_sets, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if d._lanes_state()["pending_sets"] >= n_sets:
            return
        time.sleep(0.005)
    raise AssertionError(f"never saw {n_sets} queued sets")


def test_shed_waiter_gets_prompt_typed_rejection():
    """Satellite-6 regression: admission shed raises BlsShedError in the
    CALLER within milliseconds, not after the waiter timeout."""
    d = _dispatcher(lane_caps={"attestation": 2})
    try:
        t1, o1 = _submit_bg(d, ["a1", "a2"], "attestation")
        _wait_queued(d, 2)
        t0 = time.monotonic()
        with pytest.raises(BlsShedError) as ei:
            d.verify_signature_sets(["a3"], lane="attestation")
        elapsed = time.monotonic() - t0
        assert elapsed < 1.0, f"shed took {elapsed:.1f}s — waiter-timeout ride"
        assert ei.value.lane == "attestation"
        assert ei.value.n_sets == 1
        assert "shed" in str(ei.value)
    finally:
        d.close()
        t1.join(timeout=5.0)
    # the queued waiter was resolved promptly by close(), typed the same
    assert o1 and o1[0][0] == "shed"


def test_block_lane_is_never_shed_and_evicts_attestations():
    """A block arriving into a full queue evicts queued attestations
    (prompt typed rejection for them) and is itself admitted + verified."""
    inner = MockBlsVerifier()
    d = _dispatcher(inner, pending_cap=2)
    try:
        t1, o1 = _submit_bg(d, ["a1", "a2"], "attestation")
        _wait_queued(d, 2)
        t0 = time.monotonic()
        assert d.verify_signature_sets(["b1", "b2"], lane="block") is True
        assert time.monotonic() - t0 < 5.0
        t1.join(timeout=5.0)
        assert o1 and o1[0][0] == "shed"
        assert "evicted" in str(o1[0][1])
    finally:
        d.close()


def test_eviction_stops_at_equal_or_higher_priority_lanes():
    """Overflow frees the LOWEST-priority queued sets first and leaves
    higher lanes' queues untouched once enough is freed."""
    d = _dispatcher(pending_cap=4)
    try:
        ta, oa = _submit_bg(d, ["att1", "att2"], "attestation")
        _wait_queued(d, 2)
        tg, og = _submit_bg(d, ["agg1", "agg2"], "aggregate")
        _wait_queued(d, 4)
        # +2 sync_committee sets overflow by 2 → exactly the attestation
        # entry is evicted; the aggregate entry must survive
        ts, os_ = _submit_bg(d, ["sc1", "sc2"], "sync_committee")
        ta.join(timeout=5.0)
        assert oa and oa[0][0] == "shed"
        state = d._lanes_state()
        assert state["lanes"]["attestation"]["queued_sets"] == 0
        assert state["lanes"]["aggregate"]["queued_sets"] == 2
        assert state["lanes"]["sync_committee"]["queued_sets"] == 2
    finally:
        d.close()
        for t in (tg, ts):
            t.join(timeout=5.0)
    assert og and og[0][0] == "shed"  # resolved by close, not hung
    assert os_ and os_[0][0] == "shed"


def test_batch_drains_in_strict_lane_priority_order():
    """Entries coalesce into one device batch in lane order — a block's
    sets ride ahead of an earlier-queued attestation."""
    inner = _GateVerifier()
    d = _dispatcher(inner, max_wait_ms=10, max_sigs=64)
    try:
        tp, op = _submit_bg(d, ["primer"], "aggregate")
        assert inner.started.wait(5.0)  # worker now in-flight, gated
        ta, oa = _submit_bg(d, ["att"], "attestation")
        _wait_queued(d, 1)
        tb, ob = _submit_bg(d, ["blk"], "block")
        _wait_queued(d, 2)
        inner.gate.set()
        for t in (tp, ta, tb):
            t.join(timeout=10.0)
        assert op == [("ok", True)] and oa == [("ok", True)] and ob == [("ok", True)]
        # second merged batch: block sets first despite arriving last
        assert inner.calls[0] == ["primer"]
        assert inner.calls[1] == ["blk", "att"]
    finally:
        d.close()


def test_overlap_dispatch_while_device_busy():
    """With 2 workers, a half-batch dispatches WHILE another batch is
    in flight (reason=overlap) and the overlap gauge records it."""
    inner = _GateVerifier()
    pipeline = PipelineMetrics()
    d = _dispatcher(inner, workers=2, max_wait_ms=10, max_sigs=4,
                    pipeline=pipeline)
    try:
        tp, op = _submit_bg(d, ["primer"], "aggregate")
        assert inner.started.wait(5.0)
        # 2 sets ≥ max_sigs//2 → second worker picks them up immediately
        ta, oa = _submit_bg(d, ["x1", "x2"], "attestation")
        deadline = time.monotonic() + 5.0
        while len(inner.calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(inner.calls) == 2, "overlap batch never dispatched"
        inner.gate.set()
        for t in (tp, ta):
            t.join(timeout=10.0)
        assert op == [("ok", True)] and oa == [("ok", True)]
        snap = pipeline.lanes_snapshot()
        assert snap["batches"] == 2
        assert snap["overlapped_batches"] == 1
        assert snap["overlap_fraction"] == 0.5
    finally:
        d.close()


def test_breaker_open_halves_effective_lane_caps():
    inner = MockBlsVerifier()
    inner.breaker_state = "open"  # supervised-verifier duck type
    d = _dispatcher(inner, lane_caps={"attestation": 4})
    try:
        t1, o1 = _submit_bg(d, ["a1", "a2"], "attestation")
        _wait_queued(d, 2)
        # cap 4 halves to 2 while the breaker is open: 2+1 > 2 → shed
        with pytest.raises(BlsShedError):
            d.verify_signature_sets(["a3"], lane="attestation")
        # breaker closed again: the full cap applies and the same
        # request admits (queued alongside the first entry)
        inner.breaker_state = "closed"
        t2, o2 = _submit_bg(d, ["a3"], "attestation")
        _wait_queued(d, 3)
    finally:
        d.close()
        t1.join(timeout=5.0)
        t2.join(timeout=5.0)
    assert o1 and o1[0][0] == "shed"
    assert o2 and o2[0][0] == "shed"  # resolved promptly by close()


def test_unknown_lane_routes_to_default_and_nonbatchable_bypasses():
    inner = MockBlsVerifier()
    d = _dispatcher(inner, lane_caps={DEFAULT_LANE: 1})
    try:
        # unknown lane falls back to the default lane, whose cap of 1
        # sheds this 2-set request at admission — proving the routing
        with pytest.raises(BlsShedError) as ei:
            d.verify_signature_sets(["s1", "s2"], lane="bogus_topic")
        assert ei.value.lane == DEFAULT_LANE
        # batchable=False bypasses the queue entirely (direct call)
        assert d.verify_signature_sets(["s1"], batchable=False) is True
        assert inner.sets_seen == 1
    finally:
        d.close()


def test_close_sheds_queued_waiters_and_goes_direct():
    inner = MockBlsVerifier()
    d = _dispatcher(inner)
    t1, o1 = _submit_bg(d, ["a1"], "attestation")
    _wait_queued(d, 1)
    t0 = time.monotonic()
    d.close()
    t1.join(timeout=5.0)
    assert time.monotonic() - t0 < 5.0
    assert o1 and o1[0][0] == "shed"
    assert "closed" in str(o1[0][1])
    d.close()  # idempotent
    # post-close verifies still work, routed straight to the verifier
    assert d.verify_signature_sets(["s1"], lane="attestation") is True
    assert inner.sets_seen == 1
    state = d._lanes_state()
    assert state["closed"] is True and state["pending_sets"] == 0


def test_lanes_snapshot_wiring():
    pipeline = PipelineMetrics()
    assert pipeline.lanes_snapshot() is None  # nothing bound yet
    d = _dispatcher(pipeline=pipeline, pending_cap=64,
                    lane_caps={"attestation": 8})
    try:
        snap = pipeline.lanes_snapshot()
        assert set(snap["lanes"]) == set(LANES)
        assert snap["lanes"]["attestation"]["cap"] == 8
        assert snap["pending_cap"] == 64
        assert snap["workers"] == 1
        assert snap["closed"] is False
        assert snap["sheds"] == {}
    finally:
        d.close()


def test_validation_lane_hint_capability_detection():
    """`_verify_lane` passes the lane only to facades that accept it —
    detected from the signature (incl. **kwargs), never by TypeError."""
    from lodestar_tpu.chain.validation import _verify_lane

    class _LaneAware:
        def __init__(self):
            self.lanes = []

        def verify_signature_sets(self, sets, batchable=True, lane="x"):
            self.lanes.append(lane)
            return True

    class _Kwargs:
        def __init__(self):
            self.kw = []

        def verify_signature_sets(self, sets, **kwargs):
            self.kw.append(kwargs)
            return True

    class _Legacy:
        def verify_signature_sets(self, sets):
            if len(sets) == 0:
                raise TypeError("must not be swallowed")
            return True

    aware = _LaneAware()
    assert _verify_lane(aware, ["s"], "attestation") is True
    assert aware.lanes == ["attestation"]

    kw = _Kwargs()
    assert _verify_lane(kw, ["s"], "sync_committee") is True
    assert kw.kw == [{"lane": "sync_committee"}]

    assert _verify_lane(_Legacy(), ["s"], "attestation") is True
    with pytest.raises(TypeError):
        # a TypeError raised INSIDE verification propagates untouched
        _verify_lane(_Legacy(), [], "attestation")


def test_overlap_fraction_gauge_exported_before_first_flood():
    """ISSUE 16 satellite: `lodestar_bls_lane_overlap_fraction` must be a
    live series from dispatcher construction — before the first flood,
    /debug/lanes and /metrics showed no overlap series at all, so a
    dashboard couldn't tell "no overlap yet" from "not wired"."""
    p = PipelineMetrics()
    d = _dispatcher(pipeline=p)
    try:
        assert p.lane_overlap_fraction.value() == 0.0
        assert "lodestar_bls_lane_overlap_fraction 0" in p.registry.expose()
    finally:
        d.close()


def test_submit_coalesces_a_burst_into_one_batch_without_threads():
    """`submit` queues without blocking: one caller holding a gossip
    burst submits every request, and the size trigger dispatches them as
    ONE coalesced batch whose per-request verdicts resolve afterwards."""
    inner = _GateVerifier()
    inner.gate.set()
    d = _dispatcher(inner, max_sigs=64, max_coalesce=64)
    try:
        pending = [
            d.submit([f"set{i}"], lane="attestation") for i in range(64)
        ]
        assert [r() for r in pending] == [True] * 64
        assert len(inner.calls) == 1 and len(inner.calls[0]) == 64
    finally:
        d.close()


def test_submit_raises_shed_at_admission():
    """A shed request raises at `submit`, before any resolver exists;
    queued ones resolve with the typed rejection when the dispatcher
    closes under them."""
    d = _dispatcher(lane_caps={"attestation": 2})
    queued = d.submit(["a", "b"], lane="attestation")
    with pytest.raises(BlsShedError):
        d.submit(["c"], lane="attestation")
    assert d.submit([], lane="attestation")() is False
    d.close()
    with pytest.raises(BlsShedError):
        queued()
