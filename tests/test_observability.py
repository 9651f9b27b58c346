"""Pipeline telemetry (ISSUE 1): stage timers, planner counters, queue
gauges, and the structured bench emitter.

Kernel dispatches are STUBBED at the `BatchVerifier` seam so the full
host path (marshal, planner, caches, buffering, metrics) runs in the
fast suite without paying XLA compiles; the real-kernel twin lives in
tests/test_buffered_verifier.py (slow)."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from lodestar_tpu.bls import api as bls
from lodestar_tpu import native
from lodestar_tpu.metrics import create_beacon_metrics
from lodestar_tpu.observability.bench_emit import BenchEmitter
from lodestar_tpu.observability.stages import PipelineMetrics

needs_native = pytest.mark.skipif(
    not native.HAVE_NATIVE_BLS, reason="native BLS tier unavailable"
)


def _sets(n, shared_root=True, salt=0):
    """n sets from n distinct keys; one shared signing root (the
    committee-gossip shape the root-grouped planner routes) or n
    distinct roots."""
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


def _stub_kernels(verifier, verdict=True):
    """Replace every device dispatch with a constant verdict (shapes and
    marshalling still run for real)."""
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
    # bisection-verdict seam: an all-`verdict` tree whose root reports
    # `verdict` and whose levels let the host bisect when False
    def bisect_tree(arrs, r_bits):
        m = 1 << max(0, (arrs.valid.shape[0] - 1).bit_length())
        levels = []
        n = m
        while n >= 1:
            levels.append(np.zeros((n, 2, 3, 2, 32), np.int32))
            if n == 1:
                break
            n //= 2
        return np.bool_(verdict), levels

    k.verify_bisect_tree = bisect_tree
    k.probe_nodes = lambda fs: np.full((fs.shape[0],), verdict)


# --- stage timers / planner counters -----------------------------------------


def test_stage_timer_records_and_exposes():
    p = PipelineMetrics()
    with p.stage("marshal"):
        time.sleep(0.002)
    with p.stage("dispatch") as s:
        s.bound(np.zeros(3))  # block_until_ready no-ops on host arrays
    snap = p.stage_snapshot()
    assert snap["marshal"]["count"] == 1 and snap["marshal"]["sum_s"] > 0
    assert snap["dispatch"]["count"] == 1
    text = p.registry.expose()
    assert 'lodestar_bls_pipeline_stage_seconds_bucket' in text
    assert 'stage="marshal"' in text


@needs_native
def test_planner_counters_root_grouped_path():
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    p = PipelineMetrics()
    v = TpuBlsVerifier(observer=p)
    _stub_kernels(v)
    sets = _sets(8)  # one shared root, 8 signers -> root-grouped plan
    assert v.verify_signature_sets(sets)
    assert p.planner_decisions.value(path="root_grouped") == 1
    assert p.planner_sets.value(path="root_grouped") == 8
    # one group row of 8 sets observed
    assert p.planner_group_size._totals[()] == 1
    snap = p.stage_snapshot()
    assert snap["marshal"]["count"] >= 1
    assert snap["dispatch"]["count"] >= 1
    assert snap["device_wait"]["count"] >= 1
    # dedup caches saw the pubkeys and the shared root
    assert p.cache_events.value(cache="pk", outcome="miss") == 8
    assert p.cache_events.value(cache="h2c", outcome="miss") >= 1


@needs_native
def test_planner_counters_per_set_and_individual_paths():
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    p = PipelineMetrics()
    v = TpuBlsVerifier(observer=p)
    _stub_kernels(v)
    sets = _sets(3, shared_root=False)  # distinct roots AND keys: nothing groups
    assert v.verify_signature_sets(sets)
    assert p.planner_decisions.value(path="per_set") == 1
    out = v.verify_signature_sets_individual(sets)
    assert out == [True, True, True]
    assert p.planner_decisions.value(path="individual") == 1
    # the all-valid bisection fast path: one clean batch, zero rounds
    snap = p.bisect_snapshot()
    assert snap["batches"] == {"clean": 1}
    assert snap["rounds"] == 0 and snap["probes"] == 0


def _root_sets(sizes, salt=0):
    """One run of sets per signing root, `sizes[k]` signers on root k,
    every signer a distinct key."""
    out = []
    for k, n in enumerate(sizes):
        msg = bytes([k, salt & 0xFF]) + b"\x5a" * 30
        for _ in range(n):
            sk = bls.interop_secret_key(len(out) + salt)
            out.append(bls.SignatureSet(
                pubkey=sk.to_public_key(), message=msg,
                signature=sk.sign(msg).to_bytes(),
            ))
    return out


def _priced(p, per_set, grouped, pk=100.0, **shapes):
    """A verifier of kernel `shapes` on `p`, passed a price list of
    `per_set`, `grouped` and `pk` seconds a call for every shape of each
    kernel, its dispatches stubbed."""
    from lodestar_tpu.parallel.verifier import BatchVerifier, TpuBlsVerifier

    k = BatchVerifier(**shapes)
    prices = {
        **{("per_set", (b,)): per_set for b in k.buckets},
        **{("root_grouped", c): grouped for c in k.grouped_configs},
        **{("pk_grouped", c): pk for c in k.pk_grouped_configs},
    }
    v = TpuBlsVerifier(observer=p, prices=prices, **shapes)
    _stub_kernels(v)
    return v


@needs_native
@pytest.mark.parametrize("grouped_s,path", [
    (10.0, "priced_flat"),  # one grouped call costs more than one flat call
    (0.5, "root_grouped"),
])
def test_planner_counters_priced_flat_path(grouped_s, path):
    """A groupable batch goes flat, counted `priced_flat` as well as
    `per_set`, when its per-set call costs less than the grouped call,
    and root-grouped when the grouped call costs less."""
    p = PipelineMetrics()
    v = _priced(p, 1.0, grouped_s)
    assert v.verify_signature_sets(_sets(8))  # one shared root
    decisions = p.planner_snapshot()["decisions"]
    if path == "priced_flat":
        assert decisions == {"priced_flat": 1, "per_set": 1}
    else:
        assert decisions == {"root_grouped": 1}
    assert p.planner_sets.value(path=path) == 8


@needs_native
def test_planner_counters_row_overflow_path():
    """One shared root more than the grouped rows: the fullest roots ride
    one grouped call, the rest go flat, counted `row_overflow` for the
    batch and under their own paths for the parts."""
    p = PipelineMetrics()
    # flat: 3 calls, 3.0 s; grouped 4 + 3 sets and 2 flat: 1.5 + 1.0 s
    v = _priced(p, 1.0, 1.5, buckets=(4,), grouped_configs=((2, 4),),
                pk_grouped_configs=((2, 4),))
    assert v.verify_signature_sets(_root_sets([2, 4, 3]))
    snap = p.planner_snapshot()
    assert snap["decisions"] == {
        "row_overflow": 1, "root_grouped": 1, "per_set": 1,
    }
    assert snap["sets"] == {"row_overflow": 9, "root_grouped": 7, "per_set": 2}
    # the two grouped rows are the fullest roots: 4 and 3 sets
    assert p.planner_group_size._totals[()] == 2
    assert p.planner_group_size._sums[()] == 7


@needs_native
def test_planner_price_gauge_holds_the_calibrated_prices():
    """`calibrate()` times one call per kernel shape and publishes each
    price on `lodestar_bls_planner_price_seconds{kernel,shape}`; the
    planner then prices batches with them."""
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    p = PipelineMetrics()
    v = TpuBlsVerifier(buckets=(4, 8), grouped_configs=((2, 4),),
                       pk_grouped_configs=((2, 4),), observer=p)
    _stub_kernels(v)
    for name in ("verify_batch_raw", "verify_grouped_raw",
                 "verify_pk_grouped_raw"):
        setattr(v.kernels, name,
                lambda *a, **kw: time.sleep(0.002) or np.bool_(False))
    assert v._prices is None  # uncalibrated: the unpriced order
    prices = v.calibrate()
    assert set(prices) == {
        ("per_set", (4,)), ("per_set", (8,)), ("root_grouped", (2, 4)),
        ("pk_grouped", (2, 4)),
    }
    assert v._prices == prices
    for (kernel, shape), seconds in prices.items():
        assert seconds >= 0.002
        label = "x".join(map(str, shape))
        assert p.planner_price.value(kernel=kernel, shape=label) == seconds
    text = p.registry.expose()
    assert ('lodestar_bls_planner_price_seconds{kernel="root_grouped",'
            'shape="2x4"}') in text
    # a price list passed in is published the same way
    q = PipelineMetrics()
    TpuBlsVerifier(observer=q, prices={("per_set", (4,)): 0.25})
    assert q.planner_price.value(kernel="per_set", shape="4") == 0.25


@needs_native
def test_bisect_counters_on_failed_root(monkeypatch):
    """A failed tree root walks the host bisection driver: rounds and
    probes tick, failed leaves surface as False (kernels stubbed — the
    probe reports every node failed, so every set comes back invalid)."""
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    p = PipelineMetrics()
    v = TpuBlsVerifier(observer=p)
    _stub_kernels(v, verdict=False)
    sets = _sets(4, shared_root=False)
    out = v.verify_signature_sets_individual(sets)
    assert out == [False] * 4
    snap = p.bisect_snapshot()
    assert snap["batches"] == {"bisected": 1}
    assert snap["rounds"] == 2  # log2(4) levels below the root
    assert snap["probes"] > 0
    assert p.stage_seconds._totals.get(("bisect",), 0) >= 1


@needs_native
def test_decompress_fallback_logged_and_counted():
    """A device-decompress batch the native tier can't marshal (65-byte
    message) must tick the fallback counter — the default-path downgrade
    is visible, not silent (round-6 satellite)."""
    from lodestar_tpu.chain.bls_verifier import DeviceBlsVerifier

    m = create_beacon_metrics()
    dev = DeviceBlsVerifier(observer=m.pipeline)
    _stub_kernels(dev._inner)
    assert dev._inner._device_decompress  # default-on since round 6
    sk = bls.interop_secret_key(1)
    odd_msg = b"\x55" * 65  # not a 32-byte root: native tier ineligible
    sets = [
        bls.SignatureSet(
            pubkey=sk.to_public_key(),
            message=odd_msg,
            signature=sk.sign(odd_msg).to_bytes(),
        )
    ]
    assert dev.verify_signature_sets(sets)
    assert m.pipeline.decompress_fallbacks.value() == 1
    # native-eligible batches do NOT tick the counter
    assert dev.verify_signature_sets(_sets(3))
    assert m.pipeline.decompress_fallbacks.value() == 1
    text = m.registry.expose()
    assert "lodestar_bls_verifier_decompress_fallback_total 1" in text


# --- the acceptance path: ThreadBufferedVerifier -> /metrics -----------------


@needs_native
def test_thread_buffered_device_verifier_updates_metrics_exposition():
    """verify_signature_sets through ThreadBufferedVerifier over the
    device tier updates a stage histogram, the planner-path counter and
    the queue-depth gauge, all visible on /metrics (ISSUE 1 acceptance;
    dispatches stubbed — the real-kernel twin is in the slow suite)."""
    from lodestar_tpu.chain.bls_verifier import (
        DeviceBlsVerifier,
        ThreadBufferedVerifier,
    )

    m = create_beacon_metrics()
    dev = DeviceBlsVerifier(observer=m.pipeline)
    _stub_kernels(dev._inner)
    tbv = ThreadBufferedVerifier(dev, max_sigs=6, max_wait_ms=5000, prom=m)

    # size-triggered flush: two sub-threshold requests cross max_sigs
    # together; the second caller flushes inline and resolves both
    first = []
    ta = threading.Thread(
        target=lambda: first.append(
            tbv.verify_signature_sets(_sets(3), batchable=True)
        )
    )
    ta.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and m.pipeline.buffer_depth.value() != 3:
        time.sleep(0.005)
    assert m.pipeline.buffer_depth.value() == 3  # queue gauge went up
    assert tbv.verify_signature_sets(_sets(3, salt=20), batchable=True)
    ta.join(timeout=10.0)
    assert first == [True]
    assert m.pipeline.flushes.value(reason="size") == 1

    # timer-triggered flush with a visible queue-depth transition
    tbv.max_wait = 0.15
    holder = []
    t = threading.Thread(
        target=lambda: holder.append(
            tbv.verify_signature_sets(_sets(2, salt=40), batchable=True)
        )
    )
    t.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if m.pipeline.buffer_depth.value() == 2:
            break
        time.sleep(0.005)
    assert m.pipeline.buffer_depth.value() == 2  # live callback gauge
    t.join(timeout=10.0)
    assert holder == [True]
    assert m.pipeline.buffer_depth.value() == 0
    assert m.pipeline.flushes.value(reason="timer") == 1
    assert m.pipeline.flush_seconds._totals[()] == 2

    text = m.registry.expose()
    assert "lodestar_bls_pipeline_stage_seconds_bucket" in text
    assert 'stage="marshal"' in text
    assert (
        'lodestar_bls_verifier_planner_decisions_total{path="root_grouped"}'
        in text
    )
    assert "lodestar_bls_verifier_buffer_depth 0" in text
    assert 'lodestar_bls_verifier_flushes_total{reason="size"} 1' in text
    assert 'lodestar_bls_verifier_flushes_total{reason="timer"} 1' in text


def test_metrics_server_profiler_endpoints():
    """/profiler/start|stop round-trip against stub hooks (the jax-real
    path shares the same `observability.trace` switch)."""
    import urllib.request

    from lodestar_tpu.metrics import MetricsRegistry, MetricsServer

    state = {"dir": None}

    def start(d=None):
        if state["dir"] is not None:
            return None
        state["dir"] = d or "/tmp/t"
        return state["dir"]

    def stop():
        d, state["dir"] = state["dir"], None
        return d

    server = MetricsServer(
        MetricsRegistry(), port=0, profiler_start=start, profiler_stop=stop
    )
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{url}/profiler/start?dir=/tmp/x") as r:
            assert json.load(r) == {"status": "started", "dir": "/tmp/x"}
        # double start -> 409
        try:
            urllib.request.urlopen(f"{url}/profiler/start")
            assert False, "expected 409"
        except urllib.error.HTTPError as e:
            assert e.code == 409
        with urllib.request.urlopen(f"{url}/profiler/stop") as r:
            assert json.load(r)["status"] == "stopped"
        with urllib.request.urlopen(f"{url}/metrics") as r:
            assert r.status == 200
    finally:
        server.close()


def test_metrics_server_debug_mesh_endpoint():
    """/debug/mesh serves the dispatcher snapshot when wired (round 7),
    and reports wired:false when the node serves unmeshed."""
    import urllib.request

    from lodestar_tpu.metrics import MetricsRegistry, MetricsServer

    snap = {"size": 2, "healthy": [0, 1], "evicted": []}
    server = MetricsServer(MetricsRegistry(), port=0, mesh=lambda: snap)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/mesh"
        with urllib.request.urlopen(url) as r:
            assert json.load(r) == {"wired": True, **snap}
    finally:
        server.close()

    server = MetricsServer(MetricsRegistry(), port=0, mesh=lambda: None)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/mesh"
        with urllib.request.urlopen(url) as r:
            assert json.load(r) == {"wired": False}
    finally:
        server.close()


def test_metrics_server_debug_fleet_endpoint():
    """/debug/fleet serves the two-level fleet census when wired
    (ISSUE 20), and reports wired:false on single-host/unmeshed nodes."""
    import urllib.request

    from lodestar_tpu.metrics import MetricsRegistry, MetricsServer

    snap = {
        "hosts_total": 2,
        "hosts_serving": 2,
        "layout": {"0": [0, 1], "1": [2, 3]},
        "host_dispatches": {"0": 2, "1": 2},
        "evicted_hosts": [],
        "router": {"hosts": 2, "rank": 0, "owned": 29},
    }
    server = MetricsServer(MetricsRegistry(), port=0, fleet=lambda: snap)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/fleet"
        with urllib.request.urlopen(url) as r:
            assert json.load(r) == {"wired": True, **snap}
    finally:
        server.close()

    # single-host dispatchers return None from fleet_snapshot()
    server = MetricsServer(MetricsRegistry(), port=0, fleet=lambda: None)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/fleet"
        with urllib.request.urlopen(url) as r:
            assert json.load(r) == {"wired": False}
    finally:
        server.close()


def test_metrics_server_debug_epoch_table_endpoint():
    """/debug/epoch_table serves the table snapshot when wired (ISSUE 18),
    reports wired:false when the table is disabled or absent, and maps a
    snapshot-callable failure to a 500 instead of killing the server."""
    import urllib.request

    from lodestar_tpu.metrics import MetricsRegistry, MetricsServer

    snap = {
        "enabled": True,
        "epochs_retained": 2,
        "max_rows": 64,
        "entries": [{"epoch": 7, "rows": 4, "device_resident": False}],
        "total_rows": 4,
        "evictions": 0,
        "device_put_failures": 0,
    }
    server = MetricsServer(MetricsRegistry(), port=0, epoch_table=lambda: snap)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/epoch_table"
        with urllib.request.urlopen(url) as r:
            assert json.load(r) == {"wired": True, **snap}
    finally:
        server.close()

    # knob off -> the verifier-side snapshot says enabled:false
    for snap_fn in (lambda: {"enabled": False}, lambda: None, None):
        server = MetricsServer(MetricsRegistry(), port=0, epoch_table=snap_fn)
        server.start()
        try:
            url = f"http://127.0.0.1:{server.port}/debug/epoch_table"
            with urllib.request.urlopen(url) as r:
                assert json.load(r) == {"wired": False}
        finally:
            server.close()

    def boom():
        raise RuntimeError("snapshot lock poisoned")

    server = MetricsServer(MetricsRegistry(), port=0, epoch_table=boom)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/debug/epoch_table"
        try:
            urllib.request.urlopen(url)
            assert False, "expected 500"
        except urllib.error.HTTPError as e:
            assert e.code == 500
    finally:
        server.close()


# --- bench emitter -----------------------------------------------------------


def test_bench_emitter_phase_deadline_skips_gracefully(tmp_path, capsys):
    em = BenchEmitter(
        "m", "sets/s", baseline=100.0,
        details_path=str(tmp_path / "details.json"),
    )
    with em.phase("slow", deadline_s=0.05):
        while True:  # pure-Python spin: SIGALRM interrupts it
            time.sleep(0.005)
    with em.phase("broken"):
        raise RuntimeError("boom")
    with em.phase("good") as ph:
        ph.record("sets_per_sec", 50.0)
    em.set_headline(50.0)
    doc = em.emit()
    assert doc["phases"]["slow"]["status"] == "timeout"
    assert doc["phases"]["broken"]["status"] == "error"
    assert "boom" in doc["phases"]["broken"]["error"]
    assert doc["phases"]["good"]["status"] == "ok"
    assert doc["value"] == 50.0 and doc["vs_baseline"] == 0.5
    assert doc["partial"] is True  # two phases did not complete
    # stdout carries exactly one parseable JSON line; emit() is idempotent
    assert em.emit() is None
    line = capsys.readouterr().out.strip()
    assert json.loads(line)["value"] == 50.0
    on_disk = json.load(open(tmp_path / "details.json"))
    assert on_disk["phases"]["slow"]["status"] == "timeout"


def test_bench_emitter_headline_falls_back_to_best_phase_rate(capsys):
    em = BenchEmitter("m", "sets/s")
    with em.phase("a") as ph:
        ph.record("device_sets_per_sec", 123.0)
    doc = em.emit()
    capsys.readouterr()
    assert doc["value"] == 123.0  # never null, even without set_headline
    assert doc["partial"] is True


def test_bench_emitter_sections_evaluated_at_emit_time(capsys):
    p = PipelineMetrics()
    em = BenchEmitter("m", "sets/s")
    em.add_section("planner", p.planner_snapshot)
    p.planner("per_set", 7)  # AFTER registration, BEFORE emit
    doc = em.emit()
    capsys.readouterr()
    assert doc["planner"]["decisions"] == {"per_set": 1}


def test_bench_emitter_sigterm_flush():
    """The driver's `timeout` SIGTERMs a stuck bench; the handler must
    still print the structured document (the BENCH_r05 `parsed: null`
    regression guard)."""
    code = (
        "import sys, time\n"
        f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
        "from lodestar_tpu.observability.bench_emit import BenchEmitter\n"
        "em = BenchEmitter('m', 'sets/s', baseline=10.0)\n"
        "with em.phase('spin') as ph:\n"
        "    ph.record('device_sets_per_sec', 5.0)\n"
        "    print('READY', flush=True)\n"
        "    while True:\n"
        "        time.sleep(0.02)\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=20)
    finally:
        proc.kill()
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["phases"]["spin"]["status"] == "killed"
    assert doc["value"] == 5.0  # partial results survive the kill
    assert doc["partial"] is True
    # round 7: the kill is self-labelling so bench_compare can skip the
    # truncated round instead of gating its rates
    assert doc["timed_out"] is True


def test_bench_emitter_watchdog_thread_emits_when_main_thread_is_stuck():
    """The watchdog runs on its own thread, so it emits and exits even
    when the main thread sits in a call that signal handlers cannot
    interrupt (the XLA-compile-under-SIGTERM hole)."""
    code = (
        "import sys, time\n"
        f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
        "from lodestar_tpu.observability.bench_emit import BenchEmitter\n"
        "em = BenchEmitter('m', 'sets/s', global_deadline_s=0.3)\n"
        "with em.phase('stuck'):\n"
        "    time.sleep(30)\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    out, _ = proc.communicate(timeout=20)
    assert proc.returncode == 124
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["phases"]["stuck"]["status"] == "killed"
    assert doc["watchdog_fired_after_s"] == 0.3
    assert doc["timed_out"] is True


def test_check_dashboards_lint_passes():
    """tools/check_dashboards.py: zero dashboard metric names missing
    from the registry (ISSUE 1 acceptance)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "check_dashboards.py",
    )
    spec = importlib.util.spec_from_file_location("check_dashboards", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0


def test_check_dashboards_flags_unknown_metric(tmp_path, capsys):
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "check_dashboards.py",
    )
    spec = importlib.util.spec_from_file_location("check_dashboards2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = {
        "title": "t",
        "panels": [
            {"title": "p", "targets": [{"expr": "rate(lodestar_totally_made_up_total[1m])"}]}
        ],
    }
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    assert mod.main(["check", str(tmp_path)]) == 1
    assert "lodestar_totally_made_up_total" in capsys.readouterr().out


def test_check_dashboards_flags_planted_slo_rules_violation(tmp_path, capsys):
    """ISSUE 16 satellite: the slo_rules lint catches an objective whose
    source metric no registry family declares, and a file under the
    committed-objectives floor (planted fixture)."""
    import importlib.util
    import shutil

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "check_dashboards.py",
    )
    spec = importlib.util.spec_from_file_location("check_dashboards3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dash_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "dashboards",
    )
    # real dashboards + the planted-violation rules file: only the rules
    # lint fires
    for name in os.listdir(dash_dir):
        if name != mod.SLO_RULES_FILE:
            shutil.copy(os.path.join(dash_dir, name), tmp_path / name)
    fixture = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "lint_fixtures", "slo_rules_bad.json",
    )
    shutil.copy(fixture, tmp_path / mod.SLO_RULES_FILE)
    assert mod.main(["check", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "SLO-RULES" in out
    assert "phantom_latency" in out
    assert "lodestar_bls_totally_made_up_seconds" in out
    assert "commits only 2 objectives" in out
    assert "SLO rules problem" in out
