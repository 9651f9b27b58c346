"""AOT executable store: restart without XLA in the loop (ISSUE 19).

PR 11 measured 168.1 s cold serving-ready vs 33.7 s with a warm trace
cache; these tests pin the layer that removes XLA from the restart path
entirely: serialized executables round-trip through the on-disk store,
load-before-compile serves them under the `aot_hit` classification, and
— the robustness half — every corruption mode (truncate, bit-flip,
foreign build fingerprint, partial write, format bump) degrades to a
normal JIT compile with the right outcome counter and a flight-recorder
event, never a crash and never a silently wrong executable. The slow
tier holds the subprocess cold-restart round trip for the production
grouped 16x8 shape with the `serving_ready_seconds <= 10 s` acceptance
gate, and the evicted-mesh re-dispatch that serves a pre-exported shrunk
chip set with zero new compile events.
"""

import json
import os
import struct
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from lodestar_tpu.observability.compile_ledger import (  # noqa: E402
    CompileLedger,
    timeline,
)
from lodestar_tpu.observability.flight_recorder import recorder  # noqa: E402
from lodestar_tpu.observability.stages import PipelineMetrics  # noqa: E402
from lodestar_tpu.ops import aot_store  # noqa: E402


@pytest.fixture
def store_root(tmp_path, monkeypatch):
    root = str(tmp_path / "aot")
    monkeypatch.setenv("LODESTAR_TPU_AOT_STORE", root)
    monkeypatch.delenv("LODESTAR_TPU_AOT_EXPORT", raising=False)
    monkeypatch.delenv("LODESTAR_TPU_AOT_LOAD", raising=False)
    aot_store.reset_for_tests()
    yield root
    aot_store.reset_for_tests()


def _export_tiny(kernel, monkeypatch, body=None):
    """Compile + export one tiny jitted kernel through the ledger's
    producer path; returns (artifact_path, expected_output_fn)."""
    import jax
    import jax.numpy as jnp

    body = body or (lambda x: x * 2 + 1)
    monkeypatch.setenv("LODESTAR_TPU_AOT_EXPORT", "1")
    led = CompileLedger()
    fn = led.wrap(jax.jit(body), kernel)
    out = fn(jnp.arange(8.0))
    monkeypatch.setenv("LODESTAR_TPU_AOT_EXPORT", "0")
    st = aot_store.store()
    path = st.path_for(kernel, "float32[8]")
    assert os.path.exists(path), "export must persist the artifact"
    return path, out


def _consume(kernel, body=None):
    """Fresh ledger + pipeline, one wrapped call; returns
    (output, ledger, pipeline)."""
    import jax
    import jax.numpy as jnp

    body = body or (lambda x: x * 2 + 1)
    led = CompileLedger()
    p = PipelineMetrics()
    led.attach(p)
    fn = led.wrap(jax.jit(body), kernel)
    return fn(jnp.arange(8.0)), led, p


def _rewrite_header(path, mutate):
    with open(path, "rb") as f:
        raw = f.read()
    (hlen,) = struct.unpack(">I", raw[8:12])
    header = json.loads(raw[12:12 + hlen])
    payload = raw[12 + hlen:]
    mutate(header)
    hb = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(raw[:8] + struct.pack(">I", len(hb)) + hb + payload)


# -- round trip -------------------------------------------------------------


def test_export_writes_checksummed_artifact(store_root, monkeypatch):
    path, _ = _export_tiny("t_aot_export", monkeypatch)
    st = aot_store.store()
    header = st.read_header(path)
    assert header["kernel"] == "t_aot_export"
    assert header["key"] == "float32[8]"
    assert header["fingerprint"] == st.current_fingerprint()
    assert header["payload_len"] > 0 and len(header["payload_sha256"]) == 64
    # atomic write-then-rename: no tmp residue next to the artifact
    assert all(not n.endswith(".tmp") for n in os.listdir(store_root))
    (entry,) = st.entries()
    assert entry["kernel"] == "t_aot_export" and entry["bytes"] > 0


def test_load_bypasses_jit_and_classifies_aot_hit(store_root, monkeypatch):
    import numpy as np

    _export_tiny("t_aot_roundtrip", monkeypatch)
    # consumer wraps a DIFFERENT body: a served result matching the
    # EXPORTED semantics proves the dispatch never entered the jitted fn
    out, led, p = _consume("t_aot_roundtrip", body=lambda x: x * 1000)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2 + 1)
    snap = led.snapshot()
    assert snap["aot"]["counts"] == {"hit": 1}
    assert snap["cache"]["aot_hits"] == 1
    assert [e["cache"] for e in snap["events"]] == ["aot_hit"]
    assert snap["aot"]["loaded_executables"] == 1
    text = p.registry.expose()
    assert ('lodestar_tpu_aot_events_total{kernel="t_aot_roundtrip",'
            'outcome="hit"} 1.0') in text
    # the startup timeline gained the aot_load phase on the first hit
    assert any(m["phase"] == "aot_load"
               for m in timeline().snapshot()["marks"])
    kinds = [e["kind"] for e in recorder().dump()["events"]]
    assert "aot" in kinds


def test_preload_loads_current_fingerprint_only(store_root, monkeypatch):
    import numpy as np

    path, _ = _export_tiny("t_aot_preload", monkeypatch)
    # a second artifact from a foreign build must be skipped (counted as
    # version_mismatch), not loaded
    foreign = path.replace(".aot", "_foreign.aot")
    import shutil

    shutil.copy(path, foreign)
    _rewrite_header(
        foreign, lambda h: h["fingerprint"].update({"jaxlib": "0.0.0"})
    )
    led = CompileLedger()
    summary = led.preload_aot()
    assert summary["loaded"] == ["t_aot_preload:float32[8]"]
    assert summary["skipped"] == 1
    snap = led.snapshot()
    assert snap["aot"]["counts"]["hit"] == 1
    assert snap["aot"]["counts"]["version_mismatch"] == 1
    # the preloaded executable serves without the wrapped fn compiling
    import jax

    fn = led.wrap(jax.jit(lambda x: x * -1), "t_aot_preload")
    out = fn(np.arange(8.0).astype(np.float32))
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2 + 1)


def test_store_disabled_and_load_off_are_inert(store_root, monkeypatch):
    _export_tiny("t_aot_gates", monkeypatch)
    # LOAD=0: populated store, but the consumer compiles normally
    monkeypatch.setenv("LODESTAR_TPU_AOT_LOAD", "0")
    out, led, _ = _consume("t_aot_gates")
    snap = led.snapshot()
    assert snap["aot"]["counts"] == {}
    assert snap["events"][0]["cache"] in ("hit", "miss", "off")
    # STORE=off: store() resolves to None everywhere
    monkeypatch.setenv("LODESTAR_TPU_AOT_STORE", "off")
    monkeypatch.delenv("LODESTAR_TPU_AOT_LOAD", raising=False)
    assert aot_store.store() is None
    out2, led2, _ = _consume("t_aot_gates2")
    assert led2.snapshot()["aot"]["counts"] == {}
    assert led2.preload_aot()["loaded"] == []


# -- corruption fuzz --------------------------------------------------------


def _truncate(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 100)


def _bit_flip(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size - 50)  # inside the payload
        b = f.read(1)
        f.seek(size - 50)
        f.write(bytes([b[0] ^ 0x40]))


def _wrong_fingerprint(path):
    _rewrite_header(path, lambda h: h["fingerprint"].update({"jax": "0.0.0"}))


def _partial_write(path):
    # crash mid-write of a NON-atomic writer: magic + half a header
    with open(path, "wb") as f:
        f.write(aot_store.MAGIC + struct.pack(">I", 400) + b"{\"ker")


def _bad_magic(path):
    with open(path, "r+b") as f:
        f.write(b"GARBAGE!")


def _future_format(path):
    with open(path, "r+b") as f:
        f.write(aot_store.MAGIC[:-1] + b"9")


CORRUPTIONS = [
    (_truncate, "corrupt"),
    (_bit_flip, "corrupt"),
    (_wrong_fingerprint, "version_mismatch"),
    (_partial_write, "corrupt"),
    (_bad_magic, "corrupt"),
    (_future_format, "version_mismatch"),
]


@pytest.mark.parametrize("mutate,outcome", CORRUPTIONS,
                         ids=[m.__name__.lstrip("_") for m, _ in CORRUPTIONS])
def test_corruption_degrades_to_jit(store_root, monkeypatch, mutate, outcome):
    """Every artifact failure mode falls back to a normal (correct!) JIT
    compile with the right outcome counter and a flight event — the
    acceptance criterion: no crash, no silent wrong executable."""
    import numpy as np

    kernel = f"t_aot_fuzz_{mutate.__name__.lstrip('_')}"
    path, _ = _export_tiny(kernel, monkeypatch)
    mutate(path)
    out, led, p = _consume(kernel)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2 + 1)
    snap = led.snapshot()
    assert snap["aot"]["counts"] == {outcome: 1}
    # the fallback is a REAL compile event, classified by the trace cache
    assert [e["cache"] for e in snap["events"]] != ["aot_hit"]
    assert snap["events"][0]["cache"] in ("hit", "miss", "off")
    assert (f'outcome="{outcome}"') in p.registry.expose()
    aot_events = [e for e in recorder().dump()["events"]
                  if e["kind"] == "aot" and e.get("kernel") == kernel]
    assert aot_events and aot_events[-1]["outcome"] == outcome


# -- mesh seam --------------------------------------------------------------


def _tiny_mesh_factory():
    """A stub sharded-verifier factory whose `_run` is a real jitted fn —
    the wrap seam and AOT export/load flow are exactly the production
    ones, without the minutes-long shard_map compiles."""
    import jax
    import jax.numpy as jnp

    run = jax.jit(lambda x: (x.sum() * 0 + 1).astype(jnp.int32))

    class _Stub:
        def __init__(self):
            self._run = run

        def submit(self, g, a_bits, b_bits):
            return self._run(g.pk_x)

    return lambda kind, devices, axis: _Stub()


def test_mesh_seam_prefers_jitted_run(store_root):
    from lodestar_tpu.parallel.mesh import _ledger_wrap_submit

    v = _tiny_mesh_factory()("grouped", [0, 1], "dp")
    _ledger_wrap_submit(v, "grouped", (4, 2), (0, 1))
    # the jit entry (with .lower — the AOT seam) got the wrap, the
    # submit facade stayed untouched (still the class method, unwrapped)
    assert v._run.__compile_ledger_kernel__ == "sharded_grouped"
    assert not hasattr(v.submit, "__compile_ledger_kernel__")
    assert "submit" not in vars(v)


def test_evicted_mesh_redispatch_serves_from_aot(store_root, monkeypatch):
    """The acceptance criterion: an evicted-mesh re-dispatch for an
    already-exported shrunk chip set completes with `aot_hit` and ZERO
    new compile events — the post-eviction recompile-on-the-serving-path
    cost (ROADMAP item 2) is gone when the producer exported that chip
    set."""
    import types

    import numpy as np

    import lodestar_tpu.observability.compile_ledger as cl
    from lodestar_tpu.parallel.mesh import BlsMeshDispatcher

    g = types.SimpleNamespace(pk_x=np.ones((4, 2, 3), np.float32))

    def dispatch_both_sizes(dispatcher):
        out_full = dispatcher.dispatch_grouped(g, None, None)
        dispatcher.evict(reason="test")
        out_shrunk = dispatcher.dispatch_grouped(g, None, None)
        return out_full, out_shrunk

    # producer: export the full AND the post-eviction chip set
    monkeypatch.setenv("LODESTAR_TPU_AOT_EXPORT", "1")
    monkeypatch.setattr(cl, "_ledger", CompileLedger())
    d1 = BlsMeshDispatcher(
        ["c0", "c1", "c2", "c3"], verifier_factory=_tiny_mesh_factory()
    )
    dispatch_both_sizes(d1)
    assert cl.ledger().snapshot()["aot"]["counts"]["export"] == 2

    # restarted consumer: fresh ledger, fresh dispatcher, load-only
    monkeypatch.setenv("LODESTAR_TPU_AOT_EXPORT", "0")
    monkeypatch.setattr(cl, "_ledger", CompileLedger())
    d2 = BlsMeshDispatcher(
        ["c0", "c1", "c2", "c3"], verifier_factory=_tiny_mesh_factory()
    )
    out_full, out_shrunk = dispatch_both_sizes(d2)
    assert int(out_full) == 1 and int(out_shrunk) == 1
    snap = cl.ledger().snapshot()
    assert snap["aot"]["counts"] == {"hit": 2}
    # zero NEW compiles: every ledger event this process is an aot_hit
    assert [e["cache"] for e in snap["events"]] == ["aot_hit", "aot_hit"]
    assert {e["key"] for e in snap["events"]} == {
        "(4, 2)@chips0,1,2,3", "(4, 2)@chips0,1",
    }


# -- shared prune budget ----------------------------------------------------


def test_prune_shared_budget_covers_aot_store(store_root, tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "prune_compile_cache",
        os.path.join(REPO_ROOT, "tools", "prune_compile_cache.py"),
    )
    pcc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pcc)

    cache = tmp_path / "jax_cache"
    cache.mkdir()
    os.makedirs(store_root, exist_ok=True)
    mb = 1 << 20

    def make(directory, name, size_mb, age):
        path = os.path.join(str(directory), name)
        with open(path, "wb") as f:
            f.write(b"\0" * (size_mb * mb))
        os.utime(path, (1_000_000_000 + age, 1_000_000_000 + age))
        return path

    oldest = make(cache, "trace_old", 4, age=0)
    old_aot = make(store_root, "k-aaaa.aot", 4, age=10)
    newer = make(cache, "trace_new", 4, age=20)
    newest_aot = make(store_root, "k-bbbb.aot", 4, age=30)

    r = pcc.prune(str(cache), limit_gb=9 * mb / (1 << 30),
                  aot_dir=store_root)
    # ONE LRU order across both dirs: the two oldest go, regardless of dir
    assert r["removed"] == [oldest, old_aot]
    assert r["aot_removed"] == 1
    assert sorted(r["dirs"]) == sorted([str(cache), store_root])
    assert os.path.exists(newer) and os.path.exists(newest_aot)


# -- subprocess cold restart (the acceptance number) ------------------------


PRODUCER = """
import json, os, sys
from lodestar_tpu.parallel.verifier import BatchVerifier
from lodestar_tpu.utils.jax_env import enable_compile_cache
import __graft_entry__
enable_compile_cache()
bv = BatchVerifier(grouped_configs=((16, 8),))
g, a_bits, b_bits = __graft_entry__._example_grouped(16, 8)
ok = bool(bv.verify_grouped(g, a_bits, b_bits))
from lodestar_tpu.observability.compile_ledger import ledger
print(json.dumps({"ok": ok, "aot": ledger().snapshot()["aot"]["counts"]}))
"""

CONSUMER = """
import json
# the restart path the node takes: ledger + verifier construction, AOT
# preload, serving-ready mark — executables resident, XLA never entered
from lodestar_tpu.observability.compile_ledger import ledger, timeline
from lodestar_tpu.parallel.verifier import BatchVerifier
bv = BatchVerifier(grouped_configs=((16, 8),))
summary = ledger().preload_aot()
t_ready = timeline().mark_serving_ready()
# correctness check OUTSIDE the SLO window: the loaded executable must
# produce the true verdict (workload latency, not startup)
import __graft_entry__
g, a_bits, b_bits = __graft_entry__._example_grouped(16, 8)
ok = bool(bv.verify_grouped(g, a_bits, b_bits))
snap = ledger().snapshot()
print(json.dumps({
    "serving_ready_s": t_ready,
    "loaded": summary["loaded"],
    "ok": ok,
    "aot": snap["aot"]["counts"],
    "caches": [e["cache"] for e in snap["events"]],
}))
"""


@pytest.mark.slow
def test_cold_restart_round_trip_serving_ready_slo(tmp_path):
    """Producer subprocess exports the production grouped 16x8 executable;
    a fresh consumer process loads it from disk and must be serving-ready
    within the 10 s SLO (vs the measured 33.7 s warm-trace-cache and
    168.1 s cold baselines, docs/architecture.md) — with the dispatch
    classified aot_hit and no compile event."""
    store = str(tmp_path / "aot")
    base_env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "LODESTAR_TPU_AOT_STORE": store,
        "PYTHONPATH": REPO_ROOT,
    }

    producer = subprocess.run(
        [sys.executable, "-c", PRODUCER],
        env={**base_env, "LODESTAR_TPU_AOT_EXPORT": "1"},
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=840,
    )
    assert producer.returncode == 0, producer.stderr[-2000:]
    pdoc = json.loads(producer.stdout.strip().splitlines()[-1])
    assert pdoc["ok"] and pdoc["aot"].get("export", 0) >= 1

    consumer = subprocess.run(
        [sys.executable, "-c", CONSUMER],
        env=base_env, cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert consumer.returncode == 0, consumer.stderr[-2000:]
    doc = json.loads(consumer.stdout.strip().splitlines()[-1])
    assert doc["ok"] is True
    assert doc["loaded"], "consumer must load the persisted executable"
    assert doc["aot"].get("hit", 0) >= 1 and "miss" not in doc["aot"]
    assert doc["caches"] and all(c == "aot_hit" for c in doc["caches"]), (
        f"restart must not compile: {doc['caches']}"
    )
    assert doc["serving_ready_s"] <= 10.0, (
        f"serving-ready {doc['serving_ready_s']:.1f}s blows the 10 s SLO"
    )


def test_store_and_cache_follow_jax_compilation_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, holds the compile cache AND
    the AOT store, and no code sets another directory; unset, both fall
    back to the fixed repo-local directories."""
    import jax

    from lodestar_tpu.utils import jax_env

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert jax_env.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert aot_store.default_store_dir() == str(tmp_path / "aot_store")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo_cache = jax_env.enable_compile_cache()
        assert repo_cache.endswith(os.sep + ".jax_cache")
        assert aot_store.default_store_dir().endswith(os.sep + ".aot_store")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
