"""Pipeline telemetry for the TPU BLS verifier (SURVEY §5 observability).

The reference ships prom-client metrics + the `lodestar_bls_thread_pool`
Grafana dashboard; this package is the device-pipeline equivalent,
threaded through the verifier stack:

- `stages` — stage timers (monotonic, `block_until_ready`-bounded,
  each also a span on a running profiler trace), planner-decision
  counters, cache hit counters, flush/queue gauges, and the
  device-busy-fraction sampler, all backed by `metrics.registry`
  families so they render on `/metrics`.
- `trace` — JAX profiler integration: `annotation`, the one way the
  program writes a host span into a trace, `named_scope` device-graph
  scopes (no-ops without jax), and the start/stop profiling switch
  shared by the verifier and the `/profiler/*` endpoints on the metrics
  server.
- `bench_emit` — structured bench emitter: per-phase deadlines with
  graceful skip, atexit/SIGTERM JSON flush, so a benchmark run ALWAYS
  ends in one parseable JSON document (kills the `parsed: null`
  failure mode of BENCH_r05).
- `spans` — node-wide lifecycle tracing (PR 2): trace-id/parent-id
  spans with contextvar propagation threaded from gossip decode through
  validation, BLS verify, fork choice and head update; ring-buffer
  retention served by the metrics server's `/debug/traces`; slot-
  milestone delay metrics.
- `compile_ledger` — process-wide XLA compile accounting: every compile
  at the jit/shard_map seams is a measured event (kernel, signature,
  duration, persistent-cache hit/miss) feeding the
  `lodestar_tpu_compile_*` families, `/debug/compiles`, and the
  per-run `compile_ledger.json` artifact; plus the startup timeline
  whose `serving_ready_seconds` gauge is the cold-start SLO.
- `flight_recorder` — bounded black-box ring of dispatch/compile/
  breaker/mesh/phase events, dumped into every bench emission (watchdog
  and SIGTERM paths included) so an rc=124 round leaves a post-mortem.
- `slo` — declarative SLO engine (PR 16): objectives from the committed
  `dashboards/slo_rules.json` evaluated in-process over PipelineMetrics
  with Google-SRE error budgets and multi-window (5 m/1 h) burn-rate
  states; exports `lodestar_slo_*`, serves `/debug/slo`, embeds in
  bench emissions and gates `tools/bench_compare.py`.
- `device_ledger` — device-time & memory ledger (PR 16): busy/idle/
  overlap device-seconds attributed by lane x kernel x chip from the
  lane dispatcher's flush worker and the mesh dispatch hooks, plus a
  low-rate jax memory sampler with per-chip high watermarks; serves
  `/debug/device` and lands in the rc=124 post-mortem.
"""

from .stages import (  # noqa: F401
    PLANNER_PATHS,
    STAGES,
    PipelineMetrics,
    create_pipeline_metrics,
    default_pipeline,
)
from .trace import (  # noqa: F401
    annotation,
    named_scope,
    profiling_active,
    start_profiling,
    stop_profiling,
)
from .bench_emit import BenchEmitter, PhaseTimeout  # noqa: F401
from .compile_ledger import (  # noqa: F401
    CompileLedger,
    StartupTimeline,
    ledger,
    timeline,
)
from .flight_recorder import FlightRecorder, recorder  # noqa: F401
from .slo import SloEngine  # noqa: F401
from .device_ledger import DeviceLedger  # noqa: F401
from .spans import (  # noqa: F401
    MILESTONES,
    Tracer,
    current_trace_id,
    record_slot_milestone,
    tracer,
)
