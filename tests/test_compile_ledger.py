"""Compile-ledger & cold-start observability (ISSUE 11).

The compile tax killed two driver rounds while being invisible; these
tests pin the accounting layer that makes it measurable: the wrap seam
records one event per (kernel, signature) with a persistent-cache
verdict, events fan out to every live PipelineMetrics and to
`/debug/compiles`, the startup timeline feeds the serving-ready SLO
gauge, the flight recorder survives a watchdog rc=124 as a post-mortem
inside the emitted JSON, and tools/bench_compare.py reports (but never
gates) the per-round compile-seconds delta.
"""

import importlib.util
import json
import os
import subprocess
import sys
import urllib.request

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from lodestar_tpu.observability.compile_ledger import (  # noqa: E402
    CompileLedger,
    StartupTimeline,
    ledger,
)
from lodestar_tpu.observability.flight_recorder import (  # noqa: E402
    FlightRecorder,
    recorder,
)
from lodestar_tpu.observability.stages import PipelineMetrics  # noqa: E402


def _load_tool(name):
    path = os.path.join(REPO_ROOT, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the wrap seam ----------------------------------------------------------


def test_wrap_records_one_event_per_kernel_signature():
    """First call per (kernel, signature) is a compile event; repeat
    calls with the same shape record nothing, a NEW shape records a
    second event."""
    import jax
    import jax.numpy as jnp

    led = CompileLedger()
    p = PipelineMetrics()
    led.attach(p)
    fn = led.wrap(jax.jit(lambda x: x + 1), "t_dedup_kernel")
    assert fn.__compile_ledger_kernel__ == "t_dedup_kernel"

    fn(jnp.arange(4.0))
    fn(jnp.arange(4.0))  # same signature: no second event
    snap = led.snapshot()
    assert snap["event_count"] == 1
    (event,) = snap["events"]
    assert event["kernel"] == "t_dedup_kernel"
    assert event["key"] == "float32[4]"
    assert event["seconds"] >= 0.0
    assert event["cache"] in ("off", "hit", "miss")
    assert snap["cumulative_seconds"] >= event["seconds"]

    fn(jnp.arange(8.0))  # new shape: new trace+compile, new event
    snap = led.snapshot()
    assert snap["event_count"] == 2
    assert snap["events"][1]["key"] == "float32[8]"

    # fan-out ticked the attached pipeline's families
    text = p.registry.expose()
    assert "lodestar_tpu_compile_events_total" in text
    assert 't_dedup_kernel' in text
    assert "lodestar_tpu_compile_cumulative_seconds" in text


def test_wrap_records_via_metrics_route_and_artifact(tmp_path):
    """Acceptance: a small jit driven through the PROCESS ledger seam
    shows up in (a) a live pipeline's /metrics exposition, (b) the
    /debug/compiles endpoint, (c) the compile_ledger.json artifact."""
    import jax
    import jax.numpy as jnp

    from lodestar_tpu.metrics.registry import MetricsRegistry
    from lodestar_tpu.metrics.server import MetricsServer

    p = PipelineMetrics()  # attaches itself to the global ledger
    fn = ledger().wrap(jax.jit(lambda x: x * 3), "t_route_kernel")
    fn(jnp.arange(6.0))

    text = p.registry.expose()
    assert "t_route_kernel" in text

    server = MetricsServer(MetricsRegistry())
    server.start()
    try:
        doc = json.load(
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/debug/compiles"
            )
        )
    finally:
        server.close()
    assert {"ledger", "startup", "flight_recorder"} <= set(doc)
    kernels = [e["kernel"] for e in doc["ledger"]["events"]]
    assert "t_route_kernel" in kernels
    assert doc["flight_recorder"]["capacity"] >= 1

    path = ledger().write_artifact(str(tmp_path / "compile_ledger.json"))
    saved = json.load(open(path))
    assert "t_route_kernel" in [e["kernel"] for e in saved["events"]]
    assert "cache" in saved and "cumulative_seconds" in saved


def test_static_key_records_distinct_events_per_key():
    """The mesh seam's static_key (shape@chips) must create a NEW event
    after re-wrap with a different key — the post-eviction recompile."""
    led = CompileLedger()
    calls = []
    fn_a = led.wrap(lambda: calls.append("a"), "t_mesh_kernel",
                    static_key="(64, 64)@chips0,1")
    fn_b = led.wrap(lambda: calls.append("b"), "t_mesh_kernel",
                    static_key="(64, 64)@chips0,2")
    fn_a(), fn_a(), fn_b()
    snap = led.snapshot()
    assert snap["event_count"] == 2
    assert {e["key"] for e in snap["events"]} == {
        "(64, 64)@chips0,1", "(64, 64)@chips0,2"
    }
    assert calls == ["a", "a", "b"]


@pytest.mark.parametrize("static_key", [None, "(4,)@chips0,1"])
def test_compile_ahead_serves_the_first_call(static_key):
    """`compile_ahead` records the compile event under the key the first
    call would record (the shape key, or the wrap's static key), and
    that call then runs the kept executable: no second event."""
    import jax
    import jax.numpy as jnp

    led = CompileLedger()
    fn = led.wrap(jax.jit(lambda x: x * 2), "t_ahead_kernel",
                  static_key=static_key)
    led.compile_ahead(fn, jax.ShapeDtypeStruct((4,), jnp.float32))
    led.compile_ahead(fn, jax.ShapeDtypeStruct((4,), jnp.float32))
    (event,) = led.snapshot()["events"]
    assert event["key"] == (static_key or "float32[4]")
    assert list(fn(jnp.arange(4.0))) == [0.0, 2.0, 4.0, 6.0]
    assert led.snapshot()["event_count"] == 1


def test_cache_hit_miss_classification(tmp_path):
    """Against a fresh persistent-cache dir (threshold 0 so even tiny
    kernels persist): first compile = miss (new entry appears), an
    identical fresh jit = hit (loaded from the persistent cache, no new
    entry)."""
    import jax
    import jax.numpy as jnp

    try:
        from jax._src.compilation_cache import reset_cache
    except ImportError:
        pytest.skip("jax compilation-cache reset hook unavailable")

    prev_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    prev_min = getattr(
        jax.config, "jax_persistent_cache_min_compile_time_secs", 1.0
    )
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the cache module latches its directory at first use; earlier
    # compiles in this process initialized it with the ambient config
    reset_cache()
    try:
        led = CompileLedger()
        x = jnp.arange(16.0)  # build inputs BEFORE the baseline listing
        first = led.wrap(jax.jit(lambda v: v * 2 + 1), "t_cache_first")
        first(x)
        # a NEW jit object of the same computation recompiles in-process
        # but loads from the persistent cache: no new entry => hit
        second = led.wrap(jax.jit(lambda v: v * 2 + 1), "t_cache_second")
        second(x)
        snap = led.snapshot()
        by_kernel = {e["kernel"]: e for e in snap["events"]}
        assert by_kernel["t_cache_first"]["cache"] == "miss"
        assert by_kernel["t_cache_second"]["cache"] == "hit"
        assert snap["cache"]["misses"] == 1
        assert snap["cache"]["hits"] == 1
        assert snap["cache"]["dir"] == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )
        reset_cache()


def test_batch_verifier_kernels_are_ledger_wrapped():
    """The production jit seam: every BatchVerifier kernel callable
    carries the ledger wrap (construction-time, before any dispatch)."""
    from lodestar_tpu.parallel.verifier import BatchVerifier

    bv = BatchVerifier(buckets=(4,))
    for attr, kernel in (
        ("_batch", "batch"),
        ("_individual", "individual"),
        ("_grouped", "grouped"),
        ("_pk_grouped", "pk_grouped"),
        ("_bisect_tree", "bisect_tree"),
        ("_bisect_probe", "bisect_probe"),
        # ISSUE 14: the standalone batched final exp rides the same seam
        ("_final_exp_batch", "final_exp_batch"),
        # ISSUE 15: the zero-copy wire→device kernels (on-chip signature
        # decode) are the DEFAULT serving path — their compiles must be
        # first-class ledger events too
        ("_batch_raw", "batch_raw"),
        ("_grouped_raw", "grouped_raw"),
        ("_pk_grouped_raw", "pk_grouped_raw"),
    ):
        assert getattr(bv, attr).__compile_ledger_kernel__ == kernel


def test_mesh_raw_twin_submit_is_ledger_wrapped():
    """ISSUE 15: the mesh dispatcher's raw-twin verifiers ride the same
    `_ledger_wrap_submit` seam as the limb twins — each (kind, shape,
    chips) raw verifier is one shard_map compile, recorded under the
    `sharded_grouped_raw` / `sharded_pk_grouped_raw` kernel names."""
    from lodestar_tpu.parallel.mesh import _ledger_wrap_submit

    class _V:
        def submit(self, *a):
            return True

    for kind in ("grouped_raw", "pk_grouped_raw"):
        v = _V()
        _ledger_wrap_submit(v, kind, (16, 8), (0, 1))
        assert v.submit.__compile_ledger_kernel__ == f"sharded_{kind}"


def test_fleet_twin_submit_is_ledger_wrapped_with_host_key():
    """ISSUE 20: two-level fleet verifiers record under their OWN kernel
    names (``fleet_<kind>``) with the host count in the static key — the
    same (kind, shape, chips) over 1 host vs 2 hosts is a different
    executable and the AOT store must not conflate them."""
    from lodestar_tpu.observability.compile_ledger import ledger
    from lodestar_tpu.parallel.mesh import _ledger_wrap_submit

    class _V:
        def submit(self, *a):
            return True

    for kind in ("grouped", "grouped_raw", "pk_grouped",
                 "pk_grouped_raw", "bisect"):
        v = _V()
        _ledger_wrap_submit(v, kind, (16, 8), (0, 1, 2, 3), hosts=2)
        assert v.submit.__compile_ledger_kernel__ == f"fleet_{kind}"
        assert v.submit() is True
    events = [e for e in ledger().snapshot()["events"]
              if e["kernel"].startswith("fleet_")]
    assert {e["kernel"] for e in events} >= {
        "fleet_grouped", "fleet_bisect"
    }
    for e in events:
        assert "@hosts2" in e["key"]


# -- flight recorder --------------------------------------------------------


def test_flight_recorder_ring_bounds_and_reports_drops():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("t_kind", i=i)
    dump = fr.dump()
    assert dump["capacity"] == 4
    assert dump["recorded_total"] == 10
    assert dump["dropped"] == 6
    assert [e["i"] for e in dump["events"]] == [6, 7, 8, 9]
    assert all(e["kind"] == "t_kind" for e in dump["events"])
    assert dump["events"][-1]["seq"] == 10
    limited = fr.dump(limit=2)
    assert [e["i"] for e in limited["events"]] == [8, 9]
    assert limited["dropped"] == 8


def test_flight_recorder_singleton_records_compile_events():
    """The ledger's wrap seam drops compile_start/compile_end into the
    process ring — the started-but-unfinished signature a watchdog
    post-mortem looks for."""
    led = CompileLedger()
    fn = led.wrap(lambda: None, "t_flight_kernel", static_key="k")
    fn()
    kinds = [
        (e["kind"], e.get("kernel"))
        for e in recorder().dump()["events"]
        if e.get("kernel") == "t_flight_kernel"
    ]
    assert ("compile_start", "t_flight_kernel") in kinds
    assert ("compile_end", "t_flight_kernel") in kinds


# -- startup timeline / serving-ready SLO -----------------------------------


def test_startup_timeline_marks_and_serving_ready_gauge():
    p = PipelineMetrics()  # attaches to the global ledger for fan-out
    tl = StartupTimeline()
    t1 = tl.mark("t_phase_devices")
    ready = tl.mark_serving_ready()
    assert ready >= t1 >= 0.0
    snap = tl.snapshot()
    assert snap["serving_ready_s"] == pytest.approx(ready, abs=0.01)
    phases = [m["phase"] for m in snap["marks"]]
    assert phases == ["t_phase_devices", "serving_ready"]
    text = p.registry.expose()
    assert "lodestar_tpu_serving_ready_seconds" in text
    assert 't_phase_devices' in text  # startup_phase_seconds label


def test_process_start_anchor_predates_module_import():
    """Marks measure from PROCESS start (/proc/self/stat), so the first
    mark already includes interpreter+import time — it must be visibly
    nonzero, not a fresh monotonic zero."""
    tl = StartupTimeline()
    assert tl.mark("t_anchor_check") > 0.01


# -- cache prune observability ----------------------------------------------


def test_note_prune_ticks_gauges_and_lands_in_snapshot():
    led = CompileLedger()
    p = PipelineMetrics()
    led.attach(p)
    led.note_prune({
        "entries": 10,
        "entries_remaining": 7,
        "removed": ["a", "b", "c"],
        "removed_bytes": 3 << 20,
        "total_bytes": 7 << 20,
    })
    snap = led.snapshot()
    assert snap["last_prune"]["entries_remaining"] == 7
    assert snap["last_prune"]["removed"] == 3
    assert snap["last_prune"]["removed_bytes"] == 3 << 20
    text = p.registry.expose()
    assert "lodestar_tpu_compile_cache_pruned_bytes_total" in text
    assert "lodestar_tpu_compile_cache_entries 7" in text


def test_prune_tool_emits_structured_log_and_remaining_count(
    tmp_path, capsys
):
    prune_mod = _load_tool("prune_compile_cache")
    for i in range(4):
        (tmp_path / f"entry{i}").write_bytes(b"x" * 1024)
    result = prune_mod.prune(str(tmp_path), limit_gb=2048 / (1 << 30))
    assert result["entries"] == 4
    assert result["entries_remaining"] == 4 - len(result["removed"])
    assert len(result["removed"]) == 2
    err = capsys.readouterr().err
    lines = [
        json.loads(line) for line in err.splitlines()
        if line.startswith("{")
    ]
    assert any(
        rec.get("event") == "compile_cache_prune"
        and rec["entries_remaining"] == 2
        for rec in lines
    )


def test_prune_dry_run_is_silent_and_destroys_nothing(tmp_path, capsys):
    prune_mod = _load_tool("prune_compile_cache")
    (tmp_path / "keep").write_bytes(b"x" * 4096)
    result = prune_mod.prune(str(tmp_path), limit_gb=1024 / (1 << 30),
                             dry_run=True)
    assert result["removed"] and (tmp_path / "keep").exists()
    assert "compile_cache_prune" not in capsys.readouterr().err


# -- watchdog post-mortem (end to end) --------------------------------------


def test_watchdog_rc124_leaves_flight_recorder_post_mortem(tmp_path):
    """End to end: a bench whose main thread wedges past the global
    deadline exits rc=124 but its final JSON is parseable and carries
    `timed_out`, `watchdog_fired_after_s`, and the flight-recorder dump
    naming the wedged phase; tools/bench_compare.py then SKIPS the round
    with a printed note instead of gating its partial rates."""
    code = (
        "import sys, time\n"
        f"sys.path.insert(0, {REPO_ROOT!r})\n"
        "from lodestar_tpu.observability.bench_emit import BenchEmitter\n"
        "from lodestar_tpu.observability import flight_recorder\n"
        "flight_recorder.record('dispatch', path='grouped', sets=64)\n"
        "em = BenchEmitter('m', 'sets/s', global_deadline_s=0.3)\n"
        "with em.phase('wedged_compile'):\n"
        "    time.sleep(30)\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    out, _ = proc.communicate(timeout=20)
    assert proc.returncode == 124
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["timed_out"] is True
    assert doc["watchdog_fired_after_s"] == 0.3
    assert doc["phases"]["wedged_compile"]["status"] == "killed"
    kinds = [e["kind"] for e in doc["flight_recorder"]["events"]]
    assert "dispatch" in kinds  # pre-wedge activity survived
    assert "watchdog_fired" in kinds
    phase_events = [
        e for e in doc["flight_recorder"]["events"]
        if e["kind"] == "bench_phase"
    ]
    assert phase_events and phase_events[0]["phase"] == "wedged_compile"

    # the timed-out round is skip-but-logged by the regression gate
    bench_compare = _load_tool("bench_compare")
    good = {
        "metric": "m", "value": 100.0, "unit": "sets/s",
        "phases": {"p": {"status": "ok",
                         "rows": {"device_sets_per_sec": 100.0}}},
    }
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"parsed": good}))
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"parsed": good}))
    (tmp_path / "BENCH_r03.json").write_text(
        json.dumps({"rc": 124, "parsed": doc}))
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_compare.main(["--dir", str(tmp_path)])
    report = buf.getvalue()
    assert rc == 0
    assert "skipping r03" in report and "timed out mid-run" in report
    assert "r01 -> r02" in report  # gate ran on the completed rounds


# -- build info / runtime identity ------------------------------------------


def test_runtime_info_shape_and_build_info_gauge():
    from lodestar_tpu.utils.jax_env import runtime_info

    info = runtime_info(enumerate_devices=False)
    assert set(info) == {
        "jax", "jaxlib", "backend", "device_kind", "device_count",
        "mesh_divisor", "compile_cache",
    }
    assert all(isinstance(v, str) for v in info.values())
    assert info["jax"] not in ("", "none")  # jax is importable here
    # device-free variant never initializes a backend: count stays 0
    assert info["device_count"] == "0"

    p = PipelineMetrics()
    p.set_build_info(info)
    text = p.registry.expose()
    assert "lodestar_tpu_build_info" in text
    assert f'jax="{info["jax"]}"' in text


def test_build_info_tolerates_missing_keys():
    p = PipelineMetrics()
    p.set_build_info({"jax": "0.0"})  # everything else -> "unknown"
    text = p.registry.expose()
    assert 'backend="unknown"' in text


# -- bench_compare compile-seconds delta ------------------------------------


def test_bench_compare_prints_compile_delta_without_gating(tmp_path):
    bench_compare = _load_tool("bench_compare")

    def _doc(rate, compile_s):
        return {
            "metric": "m", "value": rate, "unit": "sets/s",
            "phases": {"p": {"status": "ok",
                             "rows": {"device_sets_per_sec": rate}}},
            "compile_ledger": {"cumulative_seconds": compile_s},
        }

    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"parsed": _doc(100.0, 12.5)}))
    # compile seconds grew 40x — informational only, NEVER a regression
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"parsed": _doc(100.0, 500.0)}))
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_compare.main(["--dir", str(tmp_path)])
    report = buf.getvalue()
    assert rc == 0
    assert "cumulative compile seconds 12.5s -> 500.0s" in report
    assert "not gated" in report
    assert "OK: no gated key regressed" in report
