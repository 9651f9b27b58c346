"""Epoch-resident crypto (ISSUE 18): the `EpochPubkeyTable` LRU /
eviction / device-OOM-fallback contract, the `_pk_rows` table consult,
and the lane dispatcher's H(msg) dedup pre-warm.

Everything host-side — table bookkeeping and marshal-path lookups run
without any kernel dispatch, so the file stays in the fast tier.
"""

import threading

import numpy as np
import pytest

from lodestar_tpu import native
from lodestar_tpu.bls import api as bls
from lodestar_tpu.observability.stages import PipelineMetrics
from lodestar_tpu.parallel.epoch_table import ROW_WIDTH, EpochPubkeyTable

needs_native = pytest.mark.skipif(
    not native.HAVE_NATIVE_BLS, reason="native BLS tier unavailable"
)


def _rows(n, start=0):
    return [
        (bytes([start + i]) * 48, np.full(ROW_WIDTH, start + i, np.int32))
        for i in range(n)
    ]


def _table(**kw):
    kw.setdefault("epochs", 2)
    kw.setdefault("max_rows", 64)
    kw.setdefault("observer", PipelineMetrics())
    return EpochPubkeyTable(**kw)


def _sets(n, shared_root=True, salt=0):
    out = []
    for i in range(n):
        sk = bls.interop_secret_key(i + salt)
        msg = (
            b"\x42" * 32
            if shared_root
            else bytes([i & 0xFF, salt & 0xFF]) + b"\x17" * 30
        )
        out.append(
            bls.SignatureSet(
                pubkey=sk.to_public_key(),
                message=msg,
                signature=sk.sign(msg).to_bytes(),
            )
        )
    return out


# --- table bookkeeping -------------------------------------------------------


def test_lru_rotation_over_two_epochs():
    t = _table(epochs=2)
    assert t.populate(0, _rows(4)) == 4
    assert t.populate(1, _rows(2, start=10)) == 2
    # both retained; populating a third epoch evicts the oldest
    assert [e["epoch"] for e in t.snapshot()["entries"]] == [0, 1]
    t.populate(2, _rows(3, start=20))
    snap = t.snapshot()
    assert [e["epoch"] for e in snap["entries"]] == [1, 2]
    assert snap["evictions"] == 4  # epoch 0's rows
    # evicted epoch's keys no longer resolve; retained ones do
    assert t.lookup_rows([bytes([0]) * 48]) == [None]
    hit = t.lookup_rows([bytes([10]) * 48])[0]
    assert hit is not None and hit[0] == 10


def test_repopulating_same_epoch_replaces_not_rotates():
    t = _table(epochs=2)
    t.populate(0, _rows(4))
    t.populate(1, _rows(4, start=10))
    t.populate(1, _rows(2, start=50))  # validator set changed mid-epoch
    snap = t.snapshot()
    assert [e["epoch"] for e in snap["entries"]] == [0, 1]
    assert t.lookup_rows([bytes([10]) * 48]) == [None]
    assert t.lookup_rows([bytes([50]) * 48])[0] is not None


def test_row_cap_truncation_counts_as_evictions():
    t = _table(max_rows=3)
    assert t.populate(0, _rows(5)) == 3
    snap = t.snapshot()
    assert snap["total_rows"] == 3
    assert snap["evictions"] == 2  # the truncated tail


def test_occupancy_and_hit_miss_metrics():
    pm = PipelineMetrics()
    t = _table(observer=pm)
    t.populate(0, _rows(3))
    t.lookup_rows([bytes([0]) * 48, bytes([1]) * 48, bytes([99]) * 48])
    assert [int(v) for _, v in pm.epoch_table_hits.collect()] == [2]
    assert [int(v) for _, v in pm.epoch_table_misses.collect()] == [1]
    assert [int(v) for _, v in pm.epoch_table_occupancy_gauge.collect()] == [3]
    t.populate(1, _rows(2, start=10))
    t.populate(2, _rows(2, start=20))  # rotates epoch 0 out
    assert [int(v) for _, v in pm.epoch_table_evictions.collect()] == [3]


def test_device_put_failure_degrades_to_host_only(monkeypatch):
    import jax

    def _oom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(jax, "device_put", _oom)
    t = _table()
    assert t.populate(0, _rows(4)) == 4  # population must not raise
    snap = t.snapshot()
    assert snap["device_put_failures"] == 1
    assert snap["entries"][0]["device_resident"] is False
    # host-mirror lookups keep serving
    assert t.lookup_rows([bytes([2]) * 48])[0] is not None
    # device gather reports unavailable instead of raising
    assert t.gather_device(0, [0]) is None


def test_gather_kernel_is_ledger_wrapped():
    t = _table()
    t.populate(0, _rows(4))
    out = t.gather_device(0, np.arange(2))
    if out is None:
        pytest.skip("no device available for the gather")
    assert np.asarray(out).shape == (2, ROW_WIDTH)
    assert t._gather.__compile_ledger_kernel__ == "epoch_table"


def test_concurrent_populate_and_lookup():
    t = _table(epochs=2)
    stop = threading.Event()
    errors = []

    def reader():
        keys = [bytes([i]) * 48 for i in range(8)]
        while not stop.is_set():
            try:
                t.lookup_rows(keys)
            except Exception as e:  # pragma: no cover
                errors.append(e)
                return

    threads = [threading.Thread(target=reader, daemon=True) for _ in range(4)]
    for th in threads:
        th.start()
    for epoch in range(12):
        t.populate(epoch, _rows(8, start=epoch % 4))
    stop.set()
    for th in threads:
        th.join(timeout=5.0)
    assert not errors
    assert len(t.snapshot()["entries"]) == 2


# --- verifier integration ----------------------------------------------------


@needs_native
def test_pk_rows_served_from_table_without_decompress(monkeypatch):
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    v = TpuBlsVerifier(buckets=(4,))
    assert v._epoch_table is not None  # default-on
    sets = _sets(3)
    ref = v._pk_rows(sets)  # decompress path fills _pk_cache
    assert ref is not None
    assert v.epoch_table_populate(7, [s.pubkey.to_bytes() for s in sets]) == 3
    v._pk_cache.clear()

    def _no_decompress(*a, **k):  # pragma: no cover - must not be reached
        raise AssertionError("table hit should skip the C-tier decompress")

    monkeypatch.setattr(native, "bls_g1_decompress", _no_decompress)
    out = v._pk_rows(sets)
    assert out is not None
    assert np.array_equal(out[0], ref[0]) and np.array_equal(out[1], ref[1])


@needs_native
def test_pk_rows_falls_back_to_decompress_on_table_miss():
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    v = TpuBlsVerifier(buckets=(4,))
    v.epoch_table_populate(7, [s.pubkey.to_bytes() for s in _sets(2, salt=90)])
    sets = _sets(3)  # none of these in the table
    out = v._pk_rows(sets)
    assert out is not None and out[0].shape == (3, 32)


@needs_native
def test_device_oom_populate_still_serves_marshal_path(monkeypatch):
    """The OOM fallback chain: device_put fails -> host mirror serves
    `_pk_rows` -> and with the table fully gone the bounded `_pk_cache`
    still covers repeat keys."""
    import jax

    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    monkeypatch.setattr(
        jax, "device_put",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("oom")),
    )
    v = TpuBlsVerifier(buckets=(4,))
    sets = _sets(3, salt=40)
    assert v.epoch_table_populate(3, [s.pubkey.to_bytes() for s in sets]) == 3
    assert v.epoch_table_snapshot()["device_put_failures"] == 1
    v._pk_cache.clear()
    out = v._pk_rows(sets)  # host mirror
    assert out is not None
    v._epoch_table = None  # table lost entirely
    out2 = v._pk_rows(sets)  # _pk_cache (filled by the table hit above)
    assert out2 is not None
    assert np.array_equal(out[0], out2[0])


@needs_native
def test_epoch_table_knob_off(monkeypatch):
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    monkeypatch.setenv("LODESTAR_TPU_EPOCH_TABLE", "0")
    v = TpuBlsVerifier(buckets=(4,))
    assert v._epoch_table is None
    assert v.epoch_table_snapshot() == {"enabled": False}
    assert v.epoch_table_populate(0, [b"\x00" * 48]) == 0
    sets = _sets(2)
    assert v._pk_rows(sets) is not None  # plain _pk_cache path


@needs_native
def test_populate_skips_malformed_keys():
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    v = TpuBlsVerifier(buckets=(4,))
    good = [s.pubkey.to_bytes() for s in _sets(2)]
    assert v.epoch_table_populate(1, good + [b"\xff" * 48]) == 2


# --- dispatcher H(msg) dedup -------------------------------------------------


class _WarmRecorder:
    """Mock verifier with the `warm_h2c` seam: records pre-warm calls."""

    def __init__(self):
        self.result = True
        self.warm_calls: list[set] = []

    def verify_signature_sets(self, sets) -> bool:
        return True

    def verify_signature_sets_individual(self, sets):
        return [True] * len(sets)

    def warm_h2c(self, messages) -> int:
        self.warm_calls.append(set(messages))
        return len(messages)


class _Set:
    def __init__(self, message):
        self.message = message


def _dispatcher(verifier, **kw):
    from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher

    kw.setdefault("max_sigs", 32)
    kw.setdefault("max_wait_ms", 50)
    kw.setdefault("workers", 1)
    kw.setdefault("pending_cap", 0)
    kw.setdefault("lane_caps", {})
    kw.setdefault("pipeline", PipelineMetrics())
    return BlsLaneDispatcher(verifier, **kw)


def test_dispatcher_dedups_h2c_across_coalesced_sets():
    v = _WarmRecorder()
    pm = PipelineMetrics()
    d = _dispatcher(v, pipeline=pm)
    try:
        a, b = b"\xaa" * 32, b"\xbb" * 32
        sets = [_Set(a), _Set(a), _Set(b), _Set(a)]
        assert d.verify_signature_sets(sets, lane="aggregate")
        assert v.warm_calls == [{a, b}]  # one hash per UNIQUE root
        assert [int(x) for _, x in pm.h2c_dedup_counter.collect()] == [2]
    finally:
        d.close()


def test_dispatcher_dedup_knob_off(monkeypatch):
    monkeypatch.setenv("LODESTAR_TPU_H2C_DEDUP", "0")
    v = _WarmRecorder()
    d = _dispatcher(v)
    try:
        assert d.verify_signature_sets([_Set(b"\xaa" * 32)], lane="aggregate")
        assert v.warm_calls == []
    finally:
        d.close()


def test_dispatcher_dedup_skips_verifiers_without_seam():
    from lodestar_tpu.chain.bls_verifier import MockBlsVerifier

    d = _dispatcher(MockBlsVerifier())
    try:
        # mock sets are plain strings (no .message): dedup must no-op
        assert d.verify_signature_sets(["a1", "a2"], lane="attestation")
    finally:
        d.close()


def _stub_kernels(verifier, verdict=True):
    """Replace every device dispatch with a constant verdict (shapes and
    marshalling still run for real — the dedup claim is about the HOST
    path, which feeds the kernels identical limbs either way)."""
    k = verifier.kernels
    ret = lambda *a, **kw: np.bool_(verdict)
    k.verify_batch = ret
    k.verify_batch_raw = ret
    k.verify_grouped = ret
    k.verify_grouped_raw = ret
    k.verify_pk_grouped = ret
    k.verify_pk_grouped_raw = ret
    k.verify_individual = lambda arrs, *a, **kw: np.full(
        arrs.valid.shape, verdict
    )


@needs_native
def test_dedup_verdicts_bit_identical_on_off(monkeypatch):
    """The dedup pre-warm only pre-fills the SAME `_h2c_cache` the
    marshal path fills on demand, so verdicts (and the underlying H(m)
    limbs the kernels receive) must be bit-identical with dedup on or
    off. Kernels are stubbed at the BatchVerifier seam — dedup changes
    nothing device-side by construction; the host marshal is the claim."""
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    cold = TpuBlsVerifier(buckets=(4,))
    warm = TpuBlsVerifier(buckets=(4,))
    msg = b"\x42" * 32
    assert warm.warm_h2c([msg, msg, msg]) == 1  # one hash for three refs
    hx_cold = cold._hash_root(msg)
    hx_warm = warm._hash_root(msg)  # cache hit from the pre-warm
    assert np.array_equal(hx_cold[0], hx_warm[0])
    assert np.array_equal(hx_cold[1], hx_warm[1])
    # dispatcher-level parity: same sets through dedup on vs off, spying
    # on every H(m) limb pair the marshal path resolves
    results = {}
    for dedup in ("1", "0"):
        monkeypatch.setenv("LODESTAR_TPU_H2C_DEDUP", dedup)
        v = TpuBlsVerifier(buckets=(4,))
        _stub_kernels(v)
        hashes = []
        orig = v._hash_root

        def _spy(key, _orig=orig, _out=hashes):
            r = _orig(key)
            _out.append((key, r))
            return r

        v._hash_root = _spy
        d = _dispatcher(v)
        try:
            got = d.verify_signature_sets(_sets(3), lane="aggregate")
        finally:
            d.close()
        results[dedup] = (got, hashes)
    assert results["1"][0] == results["0"][0]
    on, off = results["1"][1], results["0"][1]
    limbs_on = {k: r for k, r in on if r is not None}
    limbs_off = {k: r for k, r in off if r is not None}
    assert set(limbs_on) == set(limbs_off)
    for k in limbs_on:
        assert np.array_equal(limbs_on[k][0], limbs_off[k][0])
        assert np.array_equal(limbs_on[k][1], limbs_off[k][1])
