"""BeaconNode composition root.

Reference: `beacon-node/src/node/nodejs.ts:127-270` — wiring order
db.start → metrics → chain → network → sync → api server → metrics server;
`close()` persists the chain state back to the db (nodejs.ts:275-290).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from ..api import BeaconApiServer
from ..api.impl import BeaconApiImpl
from ..chain import BeaconChain, CpuBlsVerifier
from ..db import BeaconDb
from ..db.controller import FileDb, MemoryDb
from ..metrics import MetricsServer, create_beacon_metrics
from ..utils.logger import get_logger
from .init_state import persist_state
from .notifier import NodeNotifier


@dataclass
class NodeOptions:
    """Reference: IBeaconNodeOptions (`node/options.ts`) — the flag tree the
    CLI maps 1:1 onto."""

    datadir: str | None = None  # None → in-memory db
    db_controller: object | None = None  # pre-opened controller wins over datadir
    rest: bool = True
    rest_port: int = 0
    rest_bearer_token: str | None = None  # require Authorization: Bearer …
    rest_cors_origin: str | None = None  # Access-Control-Allow-Origin value
    metrics: bool = False
    metrics_port: int = 0
    tpu_verifier: bool = False
    execution_engine: object | None = None
    eth1_provider: object | None = None  # IEth1Provider (mock or HTTP)
    notifier_interval_slots: int = 1


class BeaconNode:
    """Owns every service; `BeaconNode.init(...)` is the only constructor
    path (reference pattern)."""

    def __init__(self):
        raise TypeError("use BeaconNode.init()")

    @classmethod
    def init(cls, config, types, anchor_state, opts: NodeOptions | None = None):
        self = object.__new__(cls)
        opts = opts or NodeOptions()
        self.opts = opts
        self.config = config
        self.types = types
        self.log = get_logger("node")
        # cold-start timeline: marks are seconds since PROCESS start, so
        # interpreter+import time counts toward the serving-ready SLO
        from ..observability.compile_ledger import timeline

        timeline().mark("node_init")

        # 1. db
        if opts.db_controller is not None:
            controller = opts.db_controller
        else:
            controller = FileDb(opts.datadir) if opts.datadir else MemoryDb()
        self.db = BeaconDb(types, controller)

        # 2. metrics + per-validator monitor (reference validatorMonitor
        # wired at node init; register indices via monitor_validators())
        self.metrics = create_beacon_metrics()
        from ..state_transition import stf as _stf

        _stf.set_metrics(self.metrics)
        from ..metrics.validator_monitor import ValidatorMonitor

        self.validator_monitor = ValidatorMonitor(self.metrics.registry)
        from ..metrics.gc_stats import install_gc_metrics

        install_gc_metrics(self.metrics.registry)

        # 2b. lifecycle tracing: the process-wide tracer backs the metrics
        # server's /debug/traces; completed traces tick the prometheus
        # counter. Re-init (tests, in-process restart) REPLACES the node
        # hook so a dead registry stops receiving counts.
        from ..observability import spans as _spans

        self.tracer = _spans.tracer

        def _count_trace(doc, _m=self.metrics):
            kind = (doc.get("attrs") or {}).get("kind") or doc["name"]
            _m.lifecycle_traces_total.inc(kind=kind)

        _count_trace._node_wired = True
        self.tracer.on_finish[:] = [
            cb for cb in self.tracer.on_finish
            if not getattr(cb, "_node_wired", False)
        ] + [_count_trace]

        # 3. chain (verifier choice mirrors reference blsVerifyAllMainThread);
        # the device tier sits behind the cross-thread batching facade so
        # concurrent gossip-queue validations merge into device batches
        if opts.tpu_verifier:
            # the device stack (chain/verifier_stack.py): lane dispatcher
            # → supervisor (deadlines, retry, CPU-tier fallback, breaker;
            # docs/robustness.md) → device kernels, on the node registry.
            # It also turns the persistent compile cache on, loads every
            # persisted AOT executable for this build, and compiles every
            # kernel the device tier can dispatch before the verifier is
            # ready (cold, minutes on a TPU; a first dispatch that
            # compiled would outlast the supervisor's deadline)
            from ..chain.verifier_stack import build_verifier_stack

            stack = build_verifier_stack(
                self.metrics.pipeline, prom=self.metrics
            )
            self.bls_supervisor = stack.supervisor
            verifier = stack.dispatcher
            if stack.aot["loaded"]:
                self.log.info(
                    "aot store: %d executable(s) loaded in %.1fs "
                    "(restart without XLA in the loop)",
                    len(stack.aot["loaded"]), stack.aot["seconds"],
                )
            self.log.info("device verifier kernels ready: %s",
                          ", ".join(stack.compiled))
            timeline().mark("verifier_ready")
        else:
            self.bls_supervisor = None
            verifier = CpuBlsVerifier()
        # fleet ingest routing (ISSUE 20): when LODESTAR_TPU_FLEET is
        # active this host validates only its subnet slice of attestation
        # gossip, and a supervisor host-eviction rebalances the slice map
        # onto the survivors (parallel/fleet.py; wired into the gossip
        # handlers at attach_network)
        from ..parallel.fleet import FleetRouter, FleetTopology

        fleet_topo = FleetTopology.from_env()
        self.fleet_router = None
        if fleet_topo.active:
            self.fleet_router = FleetRouter(
                fleet_topo.hosts, fleet_topo.rank,
                observer=self.metrics.pipeline,
            )
            if self.bls_supervisor is not None:
                try:
                    self.bls_supervisor.fleet_attach_router(
                        self.fleet_router
                    )
                except Exception:  # noqa: BLE001 — wiring must not kill init
                    self.log.debug(
                        "fleet router mesh attach failed", exc_info=True
                    )
            self.log.info(
                "fleet ingest: rank %d/%d owns %d attestation subnet(s)",
                fleet_topo.rank, fleet_topo.hosts,
                len(self.fleet_router.slice_for()),
            )
        self.chain = BeaconChain(
            config,
            types,
            anchor_state,
            verifier=verifier,
            db=self.db,
            execution_engine=opts.execution_engine,
        )
        self.chain.metrics = self.metrics
        if hasattr(self.db.db, "metrics"):
            self.db.db.metrics = self.metrics
        self.chain.validator_monitor = self.validator_monitor

        # 3b. eth1 deposit follower (live JSON-RPC or mock; None = none)
        self.eth1_tracker = None
        if opts.eth1_provider is not None:
            from ..eth1 import Eth1DepositTracker

            self.eth1_tracker = Eth1DepositTracker(
                config, types, opts.eth1_provider
            )
            self.chain.eth1_tracker = self.eth1_tracker

        # 4. network + sync are attached by the caller once a transport
        # exists (dev mode runs networkless, like reference dev w/o peers)
        self.peers = []
        self.sync = None
        self.network = None

        # 5. servers
        self.api_server = None
        self.metrics_server = None
        if opts.rest:
            impl = BeaconApiImpl(config, types, self.chain)
            self.api_server = BeaconApiServer(
                impl, port=opts.rest_port, metrics=self.metrics,
                bearer_token=opts.rest_bearer_token,
                cors_origin=opts.rest_cors_origin,
            )
            self.api_server.start()
            self.log.info("REST API on :%d", self.api_server.port)
        # SLO engine over the node's live pipeline: /debug/slo, the
        # lodestar_slo_* families and supervisor pokes all read it
        from ..observability import device_ledger, slo

        slo.install(self.metrics.pipeline)
        if opts.metrics:
            self.metrics_server = MetricsServer(
                self.metrics.registry, port=opts.metrics_port,
                tracer=self.tracer,
                breaker=(
                    self.bls_supervisor.breaker_snapshot
                    if self.bls_supervisor is not None
                    else None
                ),
                mesh=(
                    self.bls_supervisor.mesh_snapshot
                    if self.bls_supervisor is not None
                    else None
                ),
                fleet=self._fleet_debug_snapshot,
                lanes=self.metrics.pipeline.lanes_snapshot,
                slo=slo.snapshot_or_none,
                device=device_ledger.ledger().snapshot,
                epoch_table=(
                    self.bls_supervisor.epoch_table_snapshot
                    if self.bls_supervisor is not None
                    else None
                ),
            )
            self.metrics_server.start()
            self.log.info("metrics on :%d", self.metrics_server.port)

        self.notifier = NodeNotifier(self, opts.notifier_interval_slots)

        # runtime identity on /metrics (lodestar_tpu_build_info) + the
        # serving-ready SLO mark: init returning IS this node's ready
        # point. Device enumeration only when the device tier is on — a
        # CPU-only node must not pay backend init just to label a gauge.
        from ..utils.jax_env import runtime_info

        self.metrics.pipeline.set_build_info(
            runtime_info(enumerate_devices=opts.tpu_verifier)
        )
        ready_s = timeline().mark_serving_ready()
        self.log.info("serving-ready %.2fs after process start", ready_s)
        return self

    def attach_network(self, network) -> None:
        """Bind a started Network: REST node-identity/peers routes and the
        sync layer see it (reference nodejs.ts wiring order §3.1). A
        fleet node also binds its subnet router into the gossip handlers
        so foreign-slice attestations are dropped pre-validation."""
        self.network = network
        if self.api_server is not None:
            self.api_server.impl.network = network
        handlers = getattr(network, "gossip_handlers", None)
        if self.fleet_router is not None and handlers is not None:
            from ..utils.env import env_bool

            if env_bool("LODESTAR_TPU_FLEET_INGEST"):
                handlers.fleet_router = self.fleet_router

    def _fleet_debug_snapshot(self):
        """Zero-arg provider for `/debug/fleet`: the two-level mesh census
        (with the router's slice state) when the device tier serves a
        fleet, else the bare router view, else None (wired: false)."""
        snap = None
        if self.bls_supervisor is not None:
            try:
                snap = self.bls_supervisor.fleet_snapshot()
            except Exception:  # noqa: BLE001 — debug surface must not raise
                snap = None
        if snap is None and self.fleet_router is not None:
            snap = {"router": self.fleet_router.snapshot()}
        if snap is not None:
            snap["counters"] = self.metrics.pipeline.fleet_snapshot()
        return snap

    # -- slot driving --------------------------------------------------------

    def monitor_validators(self, indices) -> None:
        """Register validator indices for per-duty tracking (reference
        --monitoredValidators flag → validatorMonitor)."""
        for i in indices:
            self.validator_monitor.register_validator(int(i))

    def on_clock_slot(self, slot: int) -> None:
        """Per-slot housekeeping: clock, fork-choice time, prepared state,
        metrics, status line."""
        self.chain.clock.set_slot(slot)
        self.chain.fork_choice.update_time(slot)
        self.chain.prepare_next_slot.on_slot(slot)
        self._follow_eth1_async()
        m = self.metrics
        m.head_slot.set(self.chain.head_state.state.slot)
        m.clock_slot.set(slot)
        m.clock_epoch.set(slot // self.config.preset.SLOTS_PER_EPOCH)
        m.head_distance.set(max(0, slot - self.chain.head_state.state.slot))
        try:
            m.active_validators.set(
                len(
                    self.chain.head_state.flat.active_indices(
                        slot // self.config.preset.SLOTS_PER_EPOCH
                    )
                )
            )
        except Exception as e:
            # phase0 test states lack the flat active-index path
            self.log.debug("active-validator gauge update failed: %s", e)
        m.current_justified_epoch.set(self.chain.justified_checkpoint[0])
        m.finalized_epoch.set(self.chain.finalized_checkpoint[0])
        m.state_cache_size.set(len(self.chain.state_cache._cache))
        m.fork_choice_nodes.set(len(self.chain.fork_choice.proto.nodes))
        m.fork_choice_votes.set(len(self.chain.fork_choice._vote_next))
        m.proposer_boost_active.set(
            1 if self.chain.fork_choice.proposer_boost_root else 0
        )
        for kind, cache in (
            ("attesters", self.chain.seen_attesters),
            ("aggregators", self.chain.seen_aggregators),
            ("block_proposers", self.chain.seen_block_proposers),
            ("aggregated", self.chain.seen_aggregated),
            ("sync_committee", self.chain.seen_sync_committee),
        ):
            try:
                m.seen_cache_size.set(len(cache._seen), kind=kind)
            except (AttributeError, TypeError):
                pass
        # h2c cache size via the DeviceBlsVerifier seam (ThreadBuffered
        # facade delegates); CpuBlsVerifier has no cache — gauge stays 0
        sizer = getattr(self.chain.bls, "h2c_cache_size", None)
        if callable(sizer):
            m.h2c_cache_size.set(sizer())
        # 0 stalled / 1 syncing / 2 synced: synced = within one slot of
        # the clock; stalled = behind AND head unchanged for >3 slots
        head = self.chain.head_state.state.slot
        if slot - head <= 1:
            m.sync_status.set(2)
            self._head_progress = (head, slot)
        else:
            last_head, last_slot = getattr(self, "_head_progress", (head, slot))
            if head > last_head:
                self._head_progress = (head, slot)
                m.sync_status.set(1)
            elif slot - last_slot > 3:
                m.sync_status.set(0)
            else:
                m.sync_status.set(1)
        pool = self.chain.attestation_pool
        m.op_pool_size.set(
            sum(len(v) for v in pool._by_slot.values())
            if hasattr(pool, "_by_slot")
            else 0,
            kind="attestations",
        )
        m.op_pool_size.set(len(self.chain.op_pool.voluntary_exits), kind="exits")
        m.op_pool_size.set(
            len(self.chain.op_pool.attester_slashings), kind="attester_slashings"
        )
        spe = self.config.preset.SLOTS_PER_EPOCH
        if slot % spe == 0:
            # epoch transition: pre-populate the device-resident pubkey
            # table for the new epoch's active set (ISSUE 18) — off the
            # slot path, one population in flight at a time
            self._populate_epoch_table_async(slot // spe)
        if slot % spe == 0 and self.validator_monitor.monitored:
            epoch_now = slot // spe
            if epoch_now >= 2:
                self.validator_monitor.on_balances(
                    epoch_now - 2, self.chain.head_state.state.balances
                )
                self.validator_monitor.log_epoch(epoch_now - 2, self.log)
        stats = getattr(self.db.db, "stats", None)
        if callable(stats):
            st = stats()
            m.db_entries.set(st["entries"])
            m.db_live_bytes.set(st["live_bytes"])
            m.db_dead_bytes.set(st["dead_bytes"])
        self.notifier.on_slot(slot)

    def _populate_epoch_table_async(self, epoch: int) -> None:
        """Decompress the epoch's active-validator pubkeys into the
        device-resident `EpochPubkeyTable` on a background thread — the
        reference's EpochContext pubkey cache, device-tier (ISSUE 18).
        Committees are fixed per epoch, so after this the attestation
        lanes read pubkey limbs with a memcpy instead of a C-tier sqrt.
        At most one population in flight; verifiers without the seam
        (CPU tier, mock) are skipped."""
        populate = getattr(self.chain.bls, "epoch_table_populate", None)
        if not callable(populate) or getattr(self, "_epoch_table_filling", False):
            return
        try:
            flat = self.chain.head_state.flat
            indices = flat.active_indices(epoch)
            pubkeys = [flat.pubkeys[int(i)].to_bytes() for i in indices]
        except Exception as e:
            # phase0 test states lack the flat active-index path
            self.log.debug("epoch-table population skipped: %s", e)
            return
        self._epoch_table_filling = True

        def _run():
            try:
                rows = populate(epoch, pubkeys)
                self.log.info(
                    "epoch table populated: epoch %d, %d rows", epoch, rows
                )
            except Exception as e:
                self.log.warning("epoch-table population failed: %s", e)
            finally:
                self._epoch_table_filling = False

        import threading

        threading.Thread(
            target=_run, name="epoch-table-fill", daemon=True
        ).start()

    def _follow_eth1_async(self) -> None:
        """Kick the deposit-log follower on a background thread, at most
        one catch-up in flight (reference: periodic eth1 update loop —
        the initial historical sync can take minutes and must never block
        the slot path or a proposal)."""
        tracker = self.eth1_tracker
        if tracker is None or getattr(self, "_eth1_following", False):
            return
        self._eth1_following = True

        def _run():
            try:
                tracker.follow()
            except Exception as e:
                self.log.warning("eth1 follow failed: %s", e)
            finally:
                self._eth1_following = False

        import threading

        threading.Thread(target=_run, name="eth1follow", daemon=True).start()

    def run(self, slots: int, slot_time: float = 0.0, on_slot=None) -> None:
        """Drive `slots` wall-clock slots (dev/test; production would follow
        the genesis-anchored clock)."""
        start = self.chain.head_state.state.slot
        for slot in range(start + 1, start + slots + 1):
            if on_slot is not None:
                on_slot(slot)
            self.on_clock_slot(slot)
            if slot_time > 0:
                time.sleep(slot_time)

    # -- shutdown ------------------------------------------------------------

    def close(self) -> None:
        """Persist the head state then stop servers (reference
        BeaconNode.close → chain.persistToDisk)."""
        try:
            head = self.chain.head_state
            head.sync_flat()
            persist_state(self.db, head.state, head.fork)
        except Exception as e:  # persist is best-effort on shutdown
            self.log.error("state persist failed: %s", e)
        if self.api_server:
            self.api_server.close()
        if self.metrics_server:
            self.metrics_server.close()
        stopper = getattr(self.chain.bls, "stop_profiling", None)
        if callable(stopper):
            stopper()  # flush the XLA trace (LODESTAR_TPU_PROFILE)
        # lane dispatcher: stop workers, shed queued waiters promptly.
        # Looked up on the TYPE so the facade's __getattr__ delegation
        # can't alias this onto the supervisor's close()
        if hasattr(type(self.chain.bls), "close"):
            try:
                self.chain.bls.close()
            except Exception as e:
                self.log.error("lane dispatcher close failed: %s", e)
        if getattr(self, "bls_supervisor", None) is not None:
            self.bls_supervisor.close()  # stop canary + dispatch worker
        self.chain._verify_pool.shutdown(wait=False)
        self.db.close()
