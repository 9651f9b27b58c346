"""Typed registry of every ``LODESTAR_TPU_*`` environment variable.

Before this module existed, ~30 knobs were read ad-hoc with `os.getenv`
scattered across the tree — three different truthiness conventions, no
single place to learn a knob exists, and nothing stopping a typo'd name
from silently reading the default forever. Every runtime read now goes
through the typed accessors below; the graftlint `env-registry` rule
(tools/lint) fails tier-1 on any raw ``os.getenv("LODESTAR_TPU_*")``
outside this file, and `tools/gen_config_docs.py` renders the registry
into `docs/configuration.md` (drift-checked in tier-1).

Conventions the registry enforces:

- **bool**: set values parse case-insensitively; ``0 / off / false / no``
  and the empty string are False, anything else is True. Unset returns
  the registered default. (This replaces the three historical idioms
  ``== "1"``, ``!= "0"`` and ``not in ("0", "off", "false")``.)
- **int / float**: unparseable or empty values fall back to the
  registered default rather than raising — a malformed knob must never
  take down a serving node (the pre-existing `_env_float` contract).
- **str**: the raw string when set (even empty), else the default.

Reading an UNREGISTERED name raises ``KeyError`` immediately: that is a
programming error, and the lint rule catches it statically as well.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "EnvVar", "REGISTRY", "env_str", "env_int", "env_float", "env_bool",
    "raw", "is_set",
]

_FALSE_VALUES = ("0", "off", "false", "no", "")


@dataclass(frozen=True)
class EnvVar:
    name: str
    type: str  # "str" | "int" | "float" | "bool"
    default: object
    doc: str  # one line; rendered into docs/configuration.md


REGISTRY: dict[str, EnvVar] = {}


def _register(name: str, type_: str, default, doc: str) -> None:
    REGISTRY[name] = EnvVar(name, type_, default, doc)


# --- kernel / math-backend selection (ops/) -------------------------------
_register("LODESTAR_TPU_LEGACY_FP", "bool", False,
          "Force the word-serial scan Fp multiplier (the CPU-backend "
          "default) instead of the dispatcher's pick.")
_register("LODESTAR_TPU_MXU_MUL", "bool", False,
          "Route Fp multiplication through the bf16 MXU matmul kernel.")
_register("LODESTAR_TPU_PALLAS_MUL", "bool", False,
          "Route Fp multiplication through the VMEM-resident Pallas "
          "kernel (ops/pallas_fp.py).")
_register("LODESTAR_TPU_PALLAS_MXU", "bool", False,
          "Route Fp multiplication through the Pallas MXU tile kernel "
          "(ops/pallas_mxu.py).")
_register("LODESTAR_TPU_PADCONV_FP", "bool", False,
          "Route Fp multiplication through the padded-convolution "
          "multiplier.")
_register("LODESTAR_TPU_PALLAS_MIN_LANES", "int", None,
          "Minimum batch lanes before the Pallas MXU kernel beats the "
          "default path; smaller batches use the fallback multiplier.")
_register("LODESTAR_TPU_FINAL_EXP_KS_CARRY", "bool", False,
          "Route the final-exp hard part's carries through the scan-free "
          "Kogge-Stone form (fp.ks_carry) inside the batched final-exp "
          "kernel only; default stays carry_scan — measured 3.5x compile "
          "and ~7.5x runtime WORSE on CPU (docs/architecture.md); the "
          "knob stays for TPU re-measurement.")
_register("LODESTAR_TPU_LAZY_FP2", "bool", True,
          "Lazy-reduction Fp2 multiplication (3 reductions -> 2); off "
          "restores the 3-full-multiply form.")
_register("LODESTAR_TPU_LAZY_FP2_MAX_ELEMS", "int", 1 << 24,
          "Element-count ceiling above which lazy Fp2 falls back to the "
          "narrow form (lazy doubles live intermediate width).")

# --- verifier / serving path ---------------------------------------------
_register("LODESTAR_TPU_DEVICE_DECOMPRESS", "bool", True,
          "On-device G2 signature decompression (default-on); off keeps "
          "the C-tier host marshal.")
_register("LODESTAR_TPU_PK_CACHE_MAX", "int", 1 << 21,
          "Bounded FIFO pubkey-decompression cache entries (~550 B "
          "each); below the active validator set it thrashes to 0% "
          "hits.")
_register("LODESTAR_TPU_EPOCH_TABLE", "bool", True,
          "Epoch-scoped device-resident pubkey table "
          "(parallel/epoch_table.py): decompressed G1 limbs for the "
          "active validator set, populated at epoch transition; off "
          "keeps the per-dispatch FIFO pubkey cache only.")
_register("LODESTAR_TPU_EPOCH_TABLE_EPOCHS", "int", 2,
          "Epoch entries the pubkey table retains (LRU rotation); the "
          "reference keeps current+next EpochContext the same way.")
_register("LODESTAR_TPU_EPOCH_TABLE_MAX_ROWS", "int", 1 << 21,
          "Row cap per epoch entry of the device pubkey table (~256 B "
          "of limb data each); populate calls beyond it are truncated "
          "and counted as evictions.")
_register("LODESTAR_TPU_H2C_DEDUP", "bool", True,
          "Hash-to-curve dedup across coalesced aggregates at the lane "
          "dispatcher: duplicate messages in one merged batch pay one "
          "hash_to_g2 (pre-warmed through the h2c cache); off restores "
          "per-request hashing.")
_register("LODESTAR_TPU_MARSHAL_THREADS", "int", None,
          "Host marshal thread-pool size override (default: cpu_count; "
          "0 disables the pool).")
_register("LODESTAR_TPU_MESH", "str", "auto",
          "Mesh serving policy: auto (multi-chip hardware only), force "
          "(any >1-device backend, incl. virtual CPU meshes), off.")
_register("LODESTAR_TPU_FLEET", "str", None,
          "Fleet (multi-host) serving: unset/off = single host; "
          "'host:port' names the jax.distributed coordinator (real "
          "multi-process fleet); 'emulate' splits the local devices "
          "into virtual hosts (CPU parity dryruns). Engages only when "
          "mesh serving itself is enabled (LODESTAR_TPU_MESH).")
_register("LODESTAR_TPU_FLEET_HOSTS", "int", 2,
          "Fleet host count: jax.distributed process count "
          "(distributed mode) or virtual-host count (emulate mode).")
_register("LODESTAR_TPU_FLEET_RANK", "int", 0,
          "This process's host rank in [0, FLEET_HOSTS); rank 0 owns "
          "the root tail of two-level dispatches.")
_register("LODESTAR_TPU_FLEET_INGEST", "bool", True,
          "When the fleet is active, drop gossip attestations whose "
          "subnet the FleetRouter assigns to another host (each host's "
          "lanes see only its slice); off validates everything locally.")
_register("LODESTAR_TPU_WAITER_TIMEOUT", "float", 300.0,
          "Seconds a buffered-verifier waiter blocks on the flush "
          "thread before escalating and failing the call.")
_register("LODESTAR_TPU_LANE_WORKERS", "int", 2,
          "Lane-dispatcher worker threads; 2 double-buffers (host "
          "marshal of batch N+1 overlaps device compute of batch N).")
_register("LODESTAR_TPU_LANE_MAX_COALESCE", "int", 64 * 512,
          "Max signature sets coalesced into one lane-dispatcher device "
          "batch (continuous batching merges in-flight requests up to "
          "this); the default fills the node's 64x512 grouped kernel.")
_register("LODESTAR_TPU_LANE_PENDING_CAP", "int", 2 * 64 * 512,
          "Global queued-set cap across all lanes; admission over it "
          "evicts lowest-priority queued work (never blocks) or sheds "
          "the incoming request.")
_register("LODESTAR_TPU_LANE_CAP_ATTESTATION", "int", 64 * 512,
          "Queued-set cap for the attestation lane (shed first under "
          "flood; the default holds one slot of unaggregated "
          "attestations on all 64 subnets at 1M validators); 0 disables "
          "the cap.")
_register("LODESTAR_TPU_LANE_CAP_AGGREGATE", "int", 1024,
          "Queued-set cap for the aggregate-and-proof lane; 0 disables "
          "the cap.")
_register("LODESTAR_TPU_LANE_CAP_SYNC_COMMITTEE", "int", 512,
          "Queued-set cap for the sync-committee lane; 0 disables the "
          "cap. The block lane is never capped or shed.")
_register("LODESTAR_TPU_IMPORT_WAIT_TIMEOUT", "float", 300.0,
          "Seconds the block-import path waits on a verification/"
          "payload future before escalating (counted in "
          "lodestar_chain_blocking_wait_timeouts_total).")
_register("LODESTAR_TPU_PRESET", "str", "mainnet",
          "Active consensus preset (mainnet | minimal).")

# --- supervisor / failure policy (chain/supervisor.py) --------------------
_register("LODESTAR_TPU_DEVICE_DEADLINE", "float", 120.0,
          "Per-dispatch device deadline in seconds; a blown deadline "
          "abandons the wedged worker and falls back.")
_register("LODESTAR_TPU_DEVICE_RETRIES", "float", 1.0,
          "Extra attempts for raised transient device errors (deadline "
          "blowouts are never retried).")
_register("LODESTAR_TPU_BREAKER_THRESHOLD", "float", 3.0,
          "Consecutive device failures that open the circuit breaker.")
_register("LODESTAR_TPU_BREAKER_COOLDOWN", "float", 30.0,
          "Seconds between canary probes while the breaker is open.")
_register("LODESTAR_TPU_AUDIT_NEGATIVE", "bool", True,
          "Re-check device-negative verdicts on the CPU oracle "
          "(corruption can fake a False but not the identity element).")

# --- observability --------------------------------------------------------
_register("LODESTAR_TPU_PROFILE", "str", None,
          "Directory for the XLA profiler trace; set = auto-start on "
          "first device dispatch.")
_register("LODESTAR_TPU_TRACE_LIFECYCLE", "bool", True,
          "Gossip-wire -> head-update lifecycle span tracing "
          "(observability/spans.py); off = shared-singleton zero-cost "
          "mode.")
_register("LODESTAR_TPU_PERSIST_INVALID", "str", None,
          "Directory to dump SSZ objects that failed import (debugging; "
          "unset = disabled).")
_register("LODESTAR_TPU_FLIGHT_RECORDER_SIZE", "int", 256,
          "Bounded event ring of the black-box flight recorder "
          "(observability/flight_recorder.py); dumped into bench "
          "documents and /debug/compiles.")
_register("LODESTAR_TPU_SLO_RULES", "str", None,
          "Path to the SLO objectives file (observability/slo.py); "
          "unset = the committed dashboards/slo_rules.json.")
_register("LODESTAR_TPU_SLO_POKE_S", "float", 1.0,
          "Min seconds between event-driven SLO re-evaluations "
          "(slo.poke() from the supervisor failure path); 0 = every "
          "poke evaluates.")
_register("LODESTAR_TPU_DEVICE_LEDGER_MEM_SAMPLE_S", "float", 10.0,
          "Min seconds between jax device-memory samples in the device "
          "ledger (observability/device_ledger.py); 0 = sampler off.")

# --- compile containment --------------------------------------------------
_register("LODESTAR_TPU_CACHE_LIMIT_GB", "float", 2.0,
          "Shared LRU byte bound across the persistent compile cache "
          "AND the AOT executable store "
          "(tools/prune_compile_cache.py).")
_register("LODESTAR_TPU_AOT_STORE", "str", None,
          "Directory of serialized AOT-compiled executables "
          "(ops/aot_store.py); 0/off/none disables the store entirely; "
          "unset = aot_store/ inside JAX_COMPILATION_CACHE_DIR when that "
          "is set, else repo-local .aot_store.")
_register("LODESTAR_TPU_AOT_LOAD", "bool", True,
          "Load persisted AOT executables before compiling (restart "
          "without XLA in the loop); off forces normal JIT even with a "
          "populated store.")
_register("LODESTAR_TPU_AOT_EXPORT", "bool", False,
          "Producer mode: first-dispatch compiles go through "
          "lower().compile() and the executable is serialized into the "
          "AOT store (tools/warmup.py --aot-export sets this).")

# --- bench / tools / tests ------------------------------------------------
_register("LODESTAR_TPU_BENCH_PHASE_DEADLINE", "float", 600.0,
          "Per-phase SIGALRM deadline in bench.py; a blown phase is "
          "skipped, not fatal.")
_register("LODESTAR_TPU_BENCH_GLOBAL_DEADLINE", "float", 840.0,
          "Bench watchdog-thread deadline; fires a partial flush marked "
          "timed_out and exits 124.")
_register("LODESTAR_TPU_FAULTS", "str", None,
          "Fault-injection plan armed at import, e.g. "
          "'exception,latency:0.05' (testing/faults.py).")
_register("LODESTAR_TPU_PERF", "bool", False,
          "Enable the perf assertion suites (tests/test_perf_suites.py).")


def _var(name: str) -> EnvVar:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name} is not a registered LODESTAR_TPU env var; declare it "
            "in lodestar_tpu/utils/env.py (the registry feeds "
            "docs/configuration.md and the env-registry lint rule)"
        ) from None


def is_set(name: str) -> bool:
    """True when the (registered) variable is present in the process env."""
    return os.environ.get(_var(name).name) is not None


def raw(name: str) -> str | None:
    """The raw string value, or None when unset. For the few knobs with
    site-specific sentinel parsing (e.g. LODESTAR_TPU_AOT_STORE's
    0/off/none disable values) — prefer the typed accessors."""
    return os.environ.get(_var(name).name)


def env_str(name: str) -> str | None:
    var = _var(name)
    value = os.environ.get(name)
    return value if value is not None else var.default


def env_int(name: str) -> int | None:
    var = _var(name)
    value = os.environ.get(name)
    if value is None:
        return var.default
    try:
        return int(value)
    except ValueError:
        return var.default


def env_float(name: str) -> float | None:
    var = _var(name)
    value = os.environ.get(name)
    if value is None:
        return var.default
    try:
        return float(value)
    except ValueError:
        return var.default


def env_bool(name: str) -> bool:
    var = _var(name)
    value = os.environ.get(name)
    if value is None:
        return bool(var.default)
    return value.strip().lower() not in _FALSE_VALUES
