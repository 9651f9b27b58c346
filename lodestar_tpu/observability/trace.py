"""JAX trace integration: named scopes + profiler lifecycle.

Two distinct scope kinds (both no-ops when jax is unavailable, so host
code can annotate unconditionally):

- `annotation(name, **meta)` — the program's one way to write a host span
  into a profiler trace: a `jax.profiler.TraceAnnotation`, on the device
  trace's clock. It records nothing while no profiler runs, and opens
  whether or not the trace was started through `start_profiling`, so a
  trace started with `jax.profiler.start_trace` sees it too. Names are a
  fixed set (`lane/...`, `stage/...`, `supervisor/...`, `compile/...`,
  `device/...`, `lifecycle/...`); counts, lanes and reasons go in `meta`,
  which the trace keeps as the event's stats.
- `named_scope(label)` — trace-time `jax.named_scope`: tags the HLO ops
  emitted under it, so device stages (MSM planes, Miller loop, final
  exponentiation) are attributable inside ONE fused XLA dispatch where
  host timers cannot see.

`start_profiling`/`stop_profiling` are the single process-wide switch —
shared by `DeviceBlsVerifier` (LODESTAR_TPU_PROFILE auto-start) and the
metrics server's `/profiler/start|stop` endpoints so neither can
double-start the XLA trace.
"""

from __future__ import annotations

import contextlib
import logging
import threading

_lock = threading.Lock()
_active_dir: str | None = None


def annotation(name: str, **meta):
    """Host-side profiler span `name` carrying `meta` as its stats;
    nullcontext when jax is unavailable."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **meta)
    except ImportError:
        return contextlib.nullcontext()


def named_scope(label: str):
    """Trace-time HLO name scope; nullcontext when jax is unavailable."""
    try:
        import jax

        return jax.named_scope(label)
    except Exception:
        return contextlib.nullcontext()


def profiling_active() -> bool:
    return _active_dir is not None


def start_profiling(trace_dir: str | None = None) -> str | None:
    """Start an XLA profiler trace into `trace_dir`; returns the directory
    actually used, or None if a trace is already running or jax/profiler
    is unavailable. Idempotent under races (one trace at a time)."""
    global _active_dir
    from ..utils.env import env_str

    trace_dir = (
        trace_dir or env_str("LODESTAR_TPU_PROFILE") or "/tmp/lodestar_tpu_profile"
    )
    with _lock:
        if _active_dir is not None:
            return None
        try:
            import jax

            jax.profiler.start_trace(trace_dir)
        except Exception:
            return None
        _active_dir = trace_dir
        return trace_dir


def stop_profiling() -> str | None:
    """Stop the running trace; returns its directory, or None if no trace
    was running."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            return None
        stopped, _active_dir = _active_dir, None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            # the switch still resets: a profiler that died mid-trace must
            # not wedge the process-wide start/stop toggle
            logging.getLogger(__name__).debug("stop_trace failed: %s", e)
        return stopped
