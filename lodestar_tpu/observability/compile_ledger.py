"""Process-wide XLA compile accounting + the cold-start timeline.

Compilation is the tax that killed both red driver rounds (ROADMAP items
1 and 5): the deep pairing kernels take minutes each on the CPU backend,
and until now that time was invisible — it only surfaced as a watchdog
rc=124. This module makes every compile a first-class, measured event:

- `CompileLedger.wrap(fn, kernel)` wraps a jitted callable at the
  construction seam (`BatchVerifier.__init__`, the mesh dispatcher's
  sharded-verifier cache). The FIRST call per
  (kernel, signature) is timed wall-clock — jax compiles synchronously
  on the first dispatch of a new shape, and execution is async, so the
  first-call wall time is dominated by trace+lower+compile. Every later
  call goes straight through with zero overhead beyond one set lookup.
- Each event records the kernel name, the shape/dtype signature key
  (or an explicit `static_key` like the mesh's `shape@chips` string),
  the device-set fingerprint, the duration, and the persistent-cache
  outcome: `miss` (a new entry appeared in the cache dir), `hit` (cache
  enabled, no new entry), `off` (no cache dir configured). Caveat: jax
  only persists compiles above `jax_persistent_cache_min_compile_time_
  secs` (default 1 s), so sub-second kernels read as `hit` — those cost
  ~nothing either way, and the minutes-long production kernels this
  ledger exists for are always persisted.
- Events tick the `lodestar_tpu_compile_*` families on every live
  `PipelineMetrics` (instances attach themselves via weakref at
  construction — node registry and the bench/tools default pipeline
  both see the same ledger), feed the flight recorder (a `compile_start`
  event lands BEFORE the call, so a wedged compile is identifiable in a
  watchdog post-mortem as started-but-unfinished), serve
  `/debug/compiles`, and persist as `compile_ledger.json` per
  bench/warmup run. The compile is also a span `compile/<kernel>` on a
  running profiler trace, and an AOT store lookup a span
  `compile/aot_lookup` (stats `kernel`, `key`, and `hit` when it loaded).

`StartupTimeline` is the getting-to-serving half: `mark(phase)` records
seconds since PROCESS start (anchored via /proc/self/stat field 22 so
python import time is included; falls back to module-import time) into
the `lodestar_tpu_startup_phase_seconds` gauge, and
`mark_serving_ready()` sets the `lodestar_tpu_serving_ready_seconds`
SLO gauge — the ROADMAP item-5 number, measured cold vs warm
`.jax_cache` (the ledger's cache section labels which one a run was).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
import weakref

from . import flight_recorder, trace

__all__ = [
    "CompileLedger",
    "StartupTimeline",
    "ledger",
    "timeline",
]

MAX_LEDGER_EVENTS = 512

_IMPORT_MONOTONIC = time.monotonic()


def _shape_key(args, kwargs) -> str:
    """Positional/keyword argument signature: dtype[shape] per array arg
    (anything without `.shape` contributes its type name). Matches what
    jax re-traces on, so one key ≈ one compiled executable."""
    parts = []
    for a in list(args) + [v for _, v in sorted(kwargs.items())]:
        shape = getattr(a, "shape", None)
        if shape is not None:
            dims = "x".join(str(d) for d in shape)
            parts.append(f"{getattr(a, 'dtype', '?')}[{dims}]")
        else:
            parts.append(type(a).__name__)
    return ",".join(parts)


_device_key_cache: str | None = None


def _device_key() -> str:
    """`<platform>x<count>` fingerprint of the visible device set, cached
    after the first (backend-initializing) lookup."""
    global _device_key_cache
    if _device_key_cache is None:
        try:
            import jax

            devices = jax.devices()
            _device_key_cache = f"{devices[0].platform}x{len(devices)}"
        except (ImportError, RuntimeError):
            _device_key_cache = "nodevice"
    return _device_key_cache


def _cache_dir() -> str | None:
    """The live persistent-cache directory, or None when disabled/unset."""
    try:
        import jax

        return getattr(jax.config, "jax_compilation_cache_dir", None) or None
    except ImportError:
        return None


def _cache_listing(cache_dir: str | None) -> frozenset:
    if not cache_dir:
        return frozenset()
    try:
        return frozenset(os.listdir(cache_dir))
    except OSError:
        return frozenset()


class CompileLedger:
    """Append-only (bounded) record of compile events + the wrap seam."""

    def __init__(self, max_events: int = MAX_LEDGER_EVENTS):
        self._lock = threading.Lock()
        self._max_events = max_events
        self._events: list[dict] = []  # guarded-by: _lock
        self._seen: set = set()  # guarded-by: _lock
        self._cumulative_s = 0.0  # guarded-by: _lock
        self._counts = {"hit": 0, "miss": 0, "off": 0}  # guarded-by: _lock
        self._pipelines: list = []  # guarded-by: _lock
        self._last_prune: dict | None = None  # guarded-by: _lock
        self._entries_at_start: int | None = None  # guarded-by: _lock
        # AOT executable overrides (ISSUE 19): (kernel, key) -> loaded
        # `jax.stages.Compiled` deserialized from ops/aot_store — once an
        # entry is here, dispatches bypass the jitted fn (and therefore
        # XLA trace/compile) entirely
        self._aot_execs: dict = {}  # guarded-by: _lock
        self._aot_counts: dict = {}  # guarded-by: _lock
        self._aot_events: list[dict] = []  # guarded-by: _lock
        self._aot_marked = False  # guarded-by: _lock (aot_load phase once)

    # -- pipeline fan-out ---------------------------------------------------

    def attach(self, pipeline) -> None:
        """Weakref-register a PipelineMetrics so ledger events tick its
        `lodestar_tpu_compile_*` families (PipelineMetrics.__init__ calls
        this; dead refs are compacted on every attach)."""
        with self._lock:
            self._pipelines = [r for r in self._pipelines if r() is not None]
            self._pipelines.append(weakref.ref(pipeline))

    def pipelines(self) -> list:
        """Every still-live attached PipelineMetrics."""
        with self._lock:
            refs = list(self._pipelines)
        return [p for p in (r() for r in refs) if p is not None]

    # -- the wrap seam ------------------------------------------------------

    def wrap(self, fn, kernel: str, static_key: str | None = None):
        """Wrap a jitted callable: the first call per (kernel, signature)
        is timed and recorded as one compile event; later calls pass
        straight through. `static_key` replaces the per-call shape key
        when the caller already knows the one signature the callable will
        ever see (the mesh's per-(shape, chips) verifiers)."""

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            key = static_key if static_key is not None else _shape_key(args, kwargs)
            with self._lock:
                # AOT override first: a loaded executable serves every
                # call for its signature without touching the jitted fn
                exec_ = self._aot_execs.get((kernel, key))
                fresh = exec_ is None and (kernel, key) not in self._seen
                if fresh:
                    # marked BEFORE the call: a concurrent second caller
                    # must not double-record, and a wedged compile must
                    # not re-record after a watchdog restart of the phase
                    self._seen.add((kernel, key))
            if exec_ is not None:
                return exec_(*args, **kwargs)
            if not fresh:
                return fn(*args, **kwargs)
            return self._timed_first_call(fn, kernel, key, args, kwargs)

        wrapped.__compile_ledger_kernel__ = kernel
        wrapped.__compile_ledger_static_key__ = static_key
        return wrapped

    def compile_ahead(self, wrapped, *shapes) -> None:
        """Compile a `wrap`ped jit callable for argument `shapes`
        (`jax.ShapeDtypeStruct`s) before its first call, and serve that
        call from the executable. The same ledger event as a first-call
        compile (AOT store first, then the persistent cache), minus the
        dispatch — so several kernels can compile on concurrent threads
        (XLA compiles release the GIL) instead of one after another on
        the serving path."""
        kernel = wrapped.__compile_ledger_kernel__
        key = wrapped.__compile_ledger_static_key__ or _shape_key(shapes, {})
        with self._lock:
            if (kernel, key) in self._seen:
                return
            self._seen.add((kernel, key))
        if self._aot_attempt(kernel, key) is not None:
            return
        fn = wrapped.__wrapped__
        self._timed_compile(
            kernel, key,
            lambda: self._keep(kernel, key, fn.lower(*shapes).compile()),
        )

    def _timed_first_call(self, fn, kernel, key, args, kwargs):
        # load-before-compile (ISSUE 19): a persisted AOT executable for
        # this exact signature + build fingerprint replaces the compile;
        # every store failure mode degrades to the normal JIT path below
        exec_ = self._aot_attempt(kernel, key)
        if exec_ is not None:
            return exec_(*args, **kwargs)
        return self._timed_compile(
            kernel, key,
            lambda: self._compile_maybe_export(fn, kernel, key, args, kwargs),
        )

    def _timed_compile(self, kernel, key, compile_fn):
        """Run `compile_fn` as one recorded compile event, classified by
        the persistent cache directory (an entry appeared = miss)."""
        cache_dir = _cache_dir()
        self._ensure_cache_baseline(cache_dir)
        before = _cache_listing(cache_dir)
        # compile_start lands in the flight recorder BEFORE the call: a
        # compile that wedges past the watchdog is identifiable in the
        # post-mortem as started-but-unfinished
        flight_recorder.record("compile_start", kernel=kernel, key=key)
        t0 = time.monotonic()
        with trace.annotation(f"compile/{kernel}", key=key):
            out = compile_fn()
        duration_s = time.monotonic() - t0
        if cache_dir is None:
            cache = "off"
        elif _cache_listing(cache_dir) - before:
            cache = "miss"
        else:
            cache = "hit"
        self.record(kernel, key, duration_s, cache)
        return out

    def _compile_maybe_export(self, fn, kernel, key, args, kwargs):
        """The first call itself. In producer mode (LODESTAR_TPU_AOT_EXPORT)
        a lowerable fn compiles via `lower().compile()` — one compile, the
        same one the plain call would do — and the executable is
        serialized into the store before dispatching. Any export failure
        degrades to the plain call: export must never fail a dispatch."""
        from ..ops import aot_store

        if not aot_store.export_enabled() or not hasattr(fn, "lower"):
            return fn(*args, **kwargs)
        try:
            compiled = fn.lower(*args, **kwargs).compile()
        except Exception as e:
            # e.g. a ledger-wrapped callable that isn't a jit entry after
            # all; the plain call still compiles + serves
            flight_recorder.record(
                "aot_export_failed", kernel=kernel, key=key,
                stage="lower", error=repr(e)[:200],
            )
            print(f"aot_store: lower/compile for export failed "
                  f"({kernel}:{key}): {e!r}", file=sys.stderr)
            return fn(*args, **kwargs)
        return self._keep(kernel, key, compiled)(*args, **kwargs)

    def _keep(self, kernel, key, compiled):
        """Serve later calls of (kernel, key) from `compiled` — identical
        semantics, and it keeps an exported artifact an exact record of
        what this process served — and, in producer mode
        (LODESTAR_TPU_AOT_EXPORT), serialize it into the AOT store.
        Export failure is recorded, never raised."""
        from ..ops import aot_store

        st = aot_store.store() if aot_store.export_enabled() else None
        if st is not None:
            t0 = time.monotonic()
            try:
                st.save(kernel, key, compiled)
            except aot_store.AotError as e:
                flight_recorder.record(
                    "aot_export_failed", kernel=kernel, key=key,
                    stage="save", error=str(e)[:200],
                )
                print(f"aot_store: export failed ({kernel}:{key}): {e}",
                      file=sys.stderr)
            else:
                self.note_aot(kernel, key, "export",
                              seconds=time.monotonic() - t0)
        with self._lock:
            self._aot_execs[(kernel, key)] = compiled
        return compiled

    # -- AOT store (ISSUE 19) ----------------------------------------------

    def _aot_attempt(self, kernel: str, key: str):
        """Try to serve (kernel, key) from the AOT store. Returns the
        loaded executable (memoized into the override map) or None; every
        failure mode is counted + flight-recorded, never raised."""
        from ..ops import aot_store

        st = aot_store.store() if aot_store.load_enabled() else None
        if st is None:
            return None
        t0 = time.monotonic()
        try:
            with trace.annotation("compile/aot_lookup", kernel=kernel,
                                  key=key) as span:
                exec_ = st.load(kernel, key)
                span.set_metadata(hit=1)
        except aot_store.AotMiss:
            self.note_aot(kernel, key, "miss")
            return None
        except aot_store.AotVersionMismatch as e:
            self.note_aot(kernel, key, "version_mismatch", detail=str(e))
            return None
        except aot_store.AotError as e:
            self.note_aot(kernel, key, "corrupt", detail=str(e))
            return None
        duration_s = time.monotonic() - t0
        with self._lock:
            self._aot_execs[(kernel, key)] = exec_
            self._seen.add((kernel, key))
            first = not self._aot_marked
            self._aot_marked = True
        if first:
            timeline().mark("aot_load")
        self.note_aot(kernel, key, "hit", seconds=duration_s)
        # aot_hit rides the compile-event stream alongside hit/miss/off:
        # the cold-start story stays in ONE place (/debug/compiles,
        # compile_ledger.json, the compile_events metric family)
        self.record(kernel, key, duration_s, cache="aot_hit")
        return exec_

    def preload_aot(self, kernels=None) -> dict:
        """Eagerly load every store artifact for the CURRENT build
        fingerprint into the override map (node restart, the cold-restart
        test): serving-ready then means every persisted signature
        dispatches without entering XLA. `kernels` optionally restricts
        to a set of kernel names. Returns a summary dict; never raises."""
        from ..ops import aot_store

        st = aot_store.store() if aot_store.load_enabled() else None
        summary: dict = {"loaded": [], "skipped": 0}
        t_start = time.monotonic()
        if st is None:
            summary["seconds"] = 0.0
            return summary
        for entry in st.entries():
            kernel, key = entry.get("kernel"), entry.get("key")
            if not kernel or key is None:
                summary["skipped"] += 1  # unreadable header: lazy path
                continue  # will classify it if the signature is dispatched
            if kernels is not None and kernel not in kernels:
                summary["skipped"] += 1
                continue
            if entry.get("fingerprint") != st.current_fingerprint():
                self.note_aot(kernel, key, "version_mismatch",
                              detail="preload: foreign build")
                summary["skipped"] += 1
                continue
            with self._lock:
                already = (kernel, key) in self._aot_execs
            if already:
                summary["skipped"] += 1
            elif self._aot_attempt(kernel, key) is None:
                summary["skipped"] += 1  # outcome already counted
            else:
                summary["loaded"].append(f"{kernel}:{key}")
        summary["seconds"] = round(time.monotonic() - t_start, 3)
        return summary

    def note_aot(self, kernel: str, key: str, outcome: str,
                 seconds: float = 0.0, detail: str | None = None) -> dict:
        """One AOT store event (hit/miss/corrupt/version_mismatch/export):
        bounded event list, flight recorder, and the
        `lodestar_tpu_aot_events_total` family on every live pipeline."""
        event = {
            "kernel": kernel,
            "key": key,
            "outcome": outcome,
            "seconds": round(seconds, 4),
        }
        if detail:
            event["detail"] = str(detail)[:200]
        with self._lock:
            self._aot_events.append(event)
            if len(self._aot_events) > self._max_events:
                del self._aot_events[0]
            self._aot_counts[outcome] = self._aot_counts.get(outcome, 0) + 1
        flight_recorder.record(
            "aot", kernel=kernel, key=key, outcome=outcome,
            seconds=event["seconds"],
        )
        for p in self.pipelines():
            p.aot_event(kernel, outcome)
        return event

    # -- recording ----------------------------------------------------------

    def record(self, kernel: str, key: str, duration_s: float,
               cache: str = "off") -> dict:
        """Append one compile event and fan it out (metrics + flight
        recorder). Public so seams that time compiles themselves (tests,
        AOT loaders) can feed the same ledger."""
        event = {
            "kernel": kernel,
            "key": key,
            "device_set": _device_key(),
            "seconds": round(duration_s, 4),
            "cache": cache,
        }
        with self._lock:
            self._events.append(event)
            if len(self._events) > self._max_events:
                del self._events[0]
            self._cumulative_s += duration_s
            self._counts[cache] = self._counts.get(cache, 0) + 1
            cumulative = self._cumulative_s
        flight_recorder.record(
            "compile_end", kernel=kernel, key=key,
            seconds=event["seconds"], cache=cache,
        )
        for p in self.pipelines():
            p.compile_event(kernel, cache, duration_s, cumulative)
        return event

    def note_prune(self, result: dict) -> None:
        """Record the last compile-cache prune (tools/prune_compile_cache)
        so the ledger artifact carries it; ticks the cache gauges on every
        live pipeline."""
        remaining = result.get(
            "entries_remaining",
            result.get("entries", 0) - len(result.get("removed", ())),
        )
        rec = {
            "entries": result.get("entries", 0),
            "entries_remaining": remaining,
            "removed": len(result.get("removed", ())),
            "removed_bytes": result.get("removed_bytes", 0),
            "total_bytes": result.get("total_bytes", 0),
            "unix_time": round(time.time(), 1),
        }
        with self._lock:
            self._last_prune = rec
        flight_recorder.record(
            "cache_prune",
            removed=rec["removed"], removed_bytes=rec["removed_bytes"],
        )
        for p in self.pipelines():
            p.cache_pruned(rec["removed_bytes"], remaining)

    # -- export -------------------------------------------------------------

    def _ensure_cache_baseline(self, cache_dir: str | None) -> None:
        """Record the cache-dir entry count once, before the first compile
        touches it — the cold/warm classifier for the serving-ready SLO."""
        if cache_dir is None:
            return
        with self._lock:
            known = self._entries_at_start is not None
        if known:
            return
        n = len(_cache_listing(cache_dir))
        with self._lock:
            if self._entries_at_start is None:
                self._entries_at_start = n

    def snapshot(self) -> dict:
        """The `/debug/compiles` + bench-section document."""
        from ..ops import aot_store

        cache_dir = _cache_dir()
        self._ensure_cache_baseline(cache_dir)
        device = _device_key()
        entries_now = len(_cache_listing(cache_dir)) if cache_dir else None
        aot_dir = aot_store.store_dir()
        with self._lock:
            events = list(self._events)
            doc = {
                "device_set": device,
                "event_count": len(events),
                "cumulative_seconds": round(self._cumulative_s, 4),
                "cache": {
                    "dir": cache_dir,
                    "entries_at_start": self._entries_at_start,
                    "entries_now": entries_now,
                    "hits": self._counts.get("hit", 0),
                    "misses": self._counts.get("miss", 0),
                    "uncached": self._counts.get("off", 0),
                    "aot_hits": self._counts.get("aot_hit", 0),
                },
                "aot": {
                    "store": aot_dir,
                    "load": aot_store.load_enabled(),
                    "export": aot_store.export_enabled(),
                    "loaded_executables": len(self._aot_execs),
                    "counts": dict(self._aot_counts),
                    "events": list(self._aot_events),
                },
                "events": events,
            }
            last_prune = self._last_prune
        if cache_dir is None:
            state = "off"
        elif not doc["cache"]["entries_at_start"]:
            state = "cold"
        else:
            state = "warm"
        doc["cache"]["state"] = state
        if last_prune is not None:
            doc["last_prune"] = dict(last_prune)
        return doc

    def write_artifact(self, path: str) -> str | None:
        """Persist the snapshot as `compile_ledger.json`; never raises —
        the artifact write must not block a bench emission."""
        try:
            with open(path, "w") as f:
                json.dump(self.snapshot(), f, indent=2)
            return path
        except OSError as e:
            print(f"compile_ledger: artifact write failed: {e}",
                  file=sys.stderr)
            return None


_ledger: CompileLedger | None = None
_ledger_lock = threading.Lock()


def ledger() -> CompileLedger:
    """The process-wide ledger every compile seam records into."""
    global _ledger
    with _ledger_lock:
        if _ledger is None:
            _ledger = CompileLedger()
        return _ledger


# -- startup timeline -------------------------------------------------------


def _process_start_monotonic() -> float:
    """The monotonic timestamp of PROCESS start (so interpreter + import
    time count toward the serving-ready SLO): /proc/self/stat field 22
    (starttime, clock ticks since boot) against /proc/uptime. Falls back
    to this module's import time off Linux."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read().decode("ascii", "replace")
        # fields after the parenthesized comm (which may contain spaces);
        # starttime is overall field 22 == index 19 of the tail
        tail = stat.rsplit(")", 1)[1].split()
        start_ticks = float(tail[19])
        hz = os.sysconf("SC_CLK_TCK")
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        age_s = uptime_s - start_ticks / hz
        if age_s < 0:
            return _IMPORT_MONOTONIC
        return time.monotonic() - age_s
    except (OSError, ValueError, IndexError):
        return _IMPORT_MONOTONIC


class StartupTimeline:
    """Phase marks measured from process start; feeds the startup-phase
    and serving-ready gauges on every live pipeline."""

    def __init__(self):
        self._lock = threading.Lock()
        self._start = _process_start_monotonic()
        self._marks: list[dict] = []  # guarded-by: _lock
        self._serving_ready_s: float | None = None  # guarded-by: _lock

    def mark(self, phase: str) -> float:
        """Record `phase` at now-since-process-start seconds."""
        t_s = time.monotonic() - self._start
        with self._lock:
            self._marks.append({"phase": phase, "t_s": round(t_s, 3)})
        flight_recorder.record("startup", phase=phase,
                               since_start_s=round(t_s, 3))
        for p in ledger().pipelines():
            p.startup_phase(phase, t_s)
        return t_s

    def mark_serving_ready(self) -> float:
        """The SLO mark: the process can serve its production dispatch
        ladder from here on (node init returned / headline kernel warm /
        warmup ladder complete)."""
        t_s = self.mark("serving_ready")
        with self._lock:
            self._serving_ready_s = t_s
        for p in ledger().pipelines():
            p.serving_ready(t_s)
        return t_s

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "marks": list(self._marks),
                "serving_ready_s": (
                    round(self._serving_ready_s, 3)
                    if self._serving_ready_s is not None
                    else None
                ),
            }


_timeline: StartupTimeline | None = None
_timeline_lock = threading.Lock()


def timeline() -> StartupTimeline:
    """The process-wide startup timeline."""
    global _timeline
    with _timeline_lock:
        if _timeline is None:
            _timeline = StartupTimeline()
        return _timeline
