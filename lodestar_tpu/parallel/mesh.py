"""Production mesh dispatch: one logical BLS verifier served by N chips
across M hosts.

`parallel/sharded.py` holds the shard_map kernels; this module is the
HOST-SIDE policy that makes them the serving path (round-7 tentpole,
generalized over hosts in ISSUE 20):

- device census → serving mesh: the largest power-of-two prefix of the
  healthy chips that divides the 64 constant Miller lanes
  (`sharded.mesh_divisor`); 1 healthy chip means "no mesh" and the
  caller's single-device kernels keep serving. With a multi-host census
  (`hosts=` rows from `fleet.FleetTopology.group_devices`) the serving
  shape becomes a TWO-LEVEL layout — a power-of-two host count × a
  uniform power-of-two chips-per-host width whose product divides 64 —
  and verifiers compile over a 2-D Mesh with a DCN axis (outer, across
  hosts) and an ICI axis (inner, within a host),
- lazy per-(kind, shape, layout) compile cache of sharded verifiers —
  an eviction changes the layout, so survivors recompile (served from
  the persistent XLA/AOT cache when warm) while the old executables stay
  keyed under the old layout for re-admission,
- the failure policy's mesh half: `evict()` removes a sick chip and
  shrinks the serving mesh (a 4-chip node keeps serving as a 3-healthy/
  2-serving mesh), `evict_host()` is the same FSM one level up — a sick
  HOST leaves the census, the fleet keeps serving on the survivors and
  the attached `FleetRouter` rebalances its gossip subnets onto them —
  and `readmit()` restores the full census (chips AND hosts) when the
  supervisor's canary passes, mirroring the reference's worker-pool
  model where a crashed worker is dropped and respawned
  (`chain/bls/multithread/index.ts`) rather than taking the node down,
- every transition and dispatch is recorded in the `lodestar_bls_mesh_*`
  and `lodestar_bls_fleet_*` families (observability/stages.py) so
  dashboards can tell a full fleet from a degraded one, and
  `testing/faults.on_mesh_dispatch`/`on_fleet_dispatch` give the chaos
  drill seams to make a chip or a whole host sick on demand.

The dispatcher itself never imports jax at module scope: unit tests
drive the eviction state machines with a stub `verifier_factory` and
fake device lists, no kernel compiles involved.
"""

from __future__ import annotations

import threading
import time as _time

from ..observability import device_ledger, trace
from ..observability.stages import PipelineMetrics, default_pipeline
from ..testing import faults as _faults
from ..utils.logger import get_logger

logger = get_logger("parallel.mesh")

__all__ = ["NOT_SHARDED", "BlsMeshDispatcher", "auto_mesh", "mesh_divisor"]

# the grouped kernels split the constant −[2^b]g1 Miller lanes across
# chips: 2·HALF_BITS of them (parallel/verifier) — the serving mesh must
# divide this count evenly
CONSTANT_LANES = 64


def mesh_divisor(n_devices: int) -> int:
    """Largest usable mesh size ≤ `n_devices`: the grouped kernels split
    the 64 constant Miller lanes across chips, so the serving mesh must
    divide 64. 64 is a power of two, so this walks powers of two — 5
    healthy chips serve as a 4-chip mesh, 3 as 2, 1 as none."""
    d = 1
    while d * 2 <= min(n_devices, CONSTANT_LANES) and CONSTANT_LANES % (d * 2) == 0:
        d *= 2
    return d

# returned by dispatch_* when this batch cannot shard (mesh too small,
# rows not divisible) — the caller falls through to its single-device
# kernel; distinct from None so a sharded `False` verdict can't be
# confused with "not handled"
NOT_SHARDED = object()


def _default_factory(kind: str, devices, axis):
    """Build the real shard_map verifier for `kind` over `devices`.

    `devices` is a flat list for a single-level mesh, or a list of
    per-host rows for a two-level fleet mesh — `np.array` then yields a
    (hosts, chips) grid and `axis` is the ``(dcn, ici)`` name pair."""
    import numpy as np
    from jax.sharding import Mesh

    from . import sharded  # deferred: keeps this module jax-free at import

    cls = {
        "grouped": sharded.ShardedGroupedVerifier,
        "grouped_raw": sharded.ShardedGroupedRawVerifier,
        "pk_grouped": sharded.ShardedPkGroupedVerifier,
        "pk_grouped_raw": sharded.ShardedPkGroupedRawVerifier,
        "bisect": sharded.ShardedBisectVerifier,
    }[kind]
    axis_names = (axis,) if isinstance(axis, str) else tuple(axis)
    return cls(Mesh(np.array(devices), axis_names=axis_names), axis)


def _ledger_wrap_submit(v, kind: str, shape, chips, hosts: int = 1) -> None:
    """Route a freshly built sharded verifier through the compile ledger:
    each (kind, shape, chip-set) verifier is exactly one shard_map
    compile, so the static key encodes shape+chips — a post-eviction mesh
    shrink recompiling on the serving path records a NEW event (the
    ROADMAP item-5 restart-story cost, now measured).

    The seam prefers the verifier's jitted `_run` over the `submit`
    facade: `_run` is the actual jit entry (it has `.lower`), which is
    what the ledger's AOT store needs to export a serialized executable —
    and what lets an evicted-mesh re-dispatch for an already-exported
    shrunk chip set load machine code from disk instead of entering XLA
    (ISSUE 19). Factory products without a rebindable `_run`/`submit`
    (test stubs with __slots__/properties) fall back or are left
    untouched.

    Two-level fleet twins record under their own kernel name
    (``fleet_<kind>``) with the host count in the static key: the same
    (kind, shape, chip-set) over 1 host vs 2 hosts is a DIFFERENT
    executable, and the AOT store must not conflate them."""
    from ..observability.compile_ledger import ledger

    kernel = f"sharded_{kind}" if hosts <= 1 else f"fleet_{kind}"
    static_key = f"{tuple(shape)}@chips{','.join(str(c) for c in chips)}"
    if hosts > 1:
        static_key += f"@hosts{hosts}"
    if getattr(v, "_run", None) is not None:
        try:
            v._run = ledger().wrap(v._run, kernel, static_key=static_key)
            return
        except AttributeError:
            logger.debug("mesh: %s verifier _run not rebindable; trying "
                         "submit", kind)
    try:
        v.submit = ledger().wrap(v.submit, kernel, static_key=static_key)
    except AttributeError:
        logger.debug("mesh: %s verifier submit not rebindable; compile "
                     "ledger seam skipped", kind)


class BlsMeshDispatcher:
    """Routes grouped/pk-grouped/bisect batches onto the serving mesh and
    owns the evict/re-admit state machine. Thread-safe: the supervisor's
    failure path and the flush thread may race."""

    def __init__(self, devices, axis: str = "dp",
                 observer: PipelineMetrics | None = None,
                 verifier_factory=None, hosts=None,
                 dcn_axis: str = "dcn", ici_axis: str = "ici",
                 router=None):
        self.axis = axis
        self.dcn_axis = dcn_axis
        self.ici_axis = ici_axis
        self.observer = observer if observer is not None else default_pipeline()
        self._factory = verifier_factory or _default_factory
        self._devices = list(devices)
        self._lock = threading.Lock()
        # chip ids are indices into the census; eviction order is recorded
        # for /debug/mesh and for "evict the most recent suspect" defaults
        self._healthy: list[int] = list(range(len(self._devices)))
        self._evicted: list[dict] = []
        # host census: rows of chip indices (fleet.FleetTopology grouping);
        # the default single row is the pre-fleet behavior bit-for-bit
        if hosts:
            claimed = [c for row in hosts for c in row]
            if sorted(claimed) != sorted(set(claimed)) or any(
                c not in self._healthy for c in claimed
            ):
                raise ValueError("hosts rows must partition distinct chips")
            self._host_map: list[list[int]] = [list(row) for row in hosts]
        else:
            self._host_map = [list(self._healthy)]
        self._evicted_hosts: list[dict] = []
        self._router = router
        self._verifiers: dict = {}
        self._dispatches = 0
        self._host_dispatches: dict[int, int] = {}
        self._publish()

    # -- census -------------------------------------------------------------

    def _serving_layout(self) -> list[list[int]]:
        """Per-host rows of the chips actually dispatched to. One row =
        single-level mesh (pre-fleet behavior). Multiple rows = a
        two-level (hosts × chips-per-host) layout: a power-of-two host
        count, a UNIFORM power-of-two per-host width (the minimum across
        surviving hosts — shard_map needs a rectangular grid), product
        capped so it divides the 64 constant lanes. Host 0 keeps the
        first row — its chip 0 owns the root tail."""
        return [row for _, row in self._serving_rows()]

    def _serving_rows(self) -> list[tuple[int, list[int]]]:
        """(host rank, serving chips) pairs — see `_serving_layout`."""
        gone = {e["host"] for e in self._evicted_hosts}
        healthy = set(self._healthy)
        rows = []
        for h, row in enumerate(self._host_map):
            if h in gone:
                continue
            hc = [c for c in row if c in healthy]
            if hc:
                rows.append((h, hc))
        if not rows:
            return []
        if len(rows) == 1:
            h, hc = rows[0]
            return [(h, hc[: mesh_divisor(len(hc))])]
        per = mesh_divisor(min(len(hc) for _, hc in rows))
        nh = 1
        while nh * 2 <= len(rows) and nh * 2 * per <= CONSTANT_LANES:
            nh *= 2
        return [(h, hc[:per]) for h, hc in rows[:nh]]

    @property
    def size(self) -> int:
        """Current serving-mesh size (total chips actually dispatched to,
        across every serving host)."""
        return sum(len(r) for r in self._serving_layout())

    @property
    def hosts_serving(self) -> int:
        return len(self._serving_layout())

    @property
    def hosts_total(self) -> int:
        return len(self._host_map)

    @property
    def enabled(self) -> bool:
        return self.size >= 2

    def _serving_chips(self) -> list[int]:
        return [c for row in self._serving_layout() for c in row]

    def _publish(self) -> None:
        self.observer.mesh_state(self.size, len(self._evicted))
        if len(self._host_map) > 1:
            self.observer.fleet_state(
                self.hosts_serving, len(self._evicted_hosts)
            )

    def attach_router(self, router) -> None:
        """Bind the FleetRouter whose subnet slices must follow host
        evictions (node wiring; tests pass router= directly)."""
        self._router = router

    # -- verifier cache -----------------------------------------------------

    def _verifier(self, kind: str, shape):
        with self._lock:
            rows = self._serving_rows()
            chips = tuple(c for _, row in rows for c in row)
            # keyed by the full (host rank, chip set) layout: the same
            # chip set regrouped under different hosts is a different
            # device assignment, hence a different executable
            key = (kind, shape, tuple((h, tuple(r)) for h, r in rows))
            v = self._verifiers.get(key)
            if v is None:
                if len(rows) > 1:
                    devs = [
                        [self._devices[c] for c in row] for _, row in rows
                    ]
                    ax = (self.dcn_axis, self.ici_axis)
                else:
                    devs = [self._devices[c] for c in chips]
                    ax = self.axis
                v = self._factory(kind, devs, ax)
                _ledger_wrap_submit(v, kind, shape, chips, hosts=len(rows))
                self._verifiers[key] = v
            return v, chips, rows

    def compile_target(self, kind: str, shape):
        """(ledger-wrapped jit entry, input sharding) of the sharded
        verifier that a `kind` batch of `shape` dispatches to on the
        current serving mesh: what `TpuBlsVerifier.precompile` compiles
        ahead of the first dispatch."""
        v, _, _ = self._verifier(kind, tuple(shape))
        return v._run, v._sharding

    # -- dispatch -----------------------------------------------------------

    def _pre_dispatch(self, kind: str, chips, rows) -> None:
        _faults.on_mesh_dispatch(len(chips))
        if len(rows) > 1:
            _faults.on_fleet_dispatch([h for h, _ in rows])
        with self._lock:
            self._dispatches += 1
            if len(rows) > 1:
                for h, _ in rows:
                    self._host_dispatches[h] = (
                        self._host_dispatches.get(h, 0) + 1
                    )
        self.observer.mesh_dispatch(chips)
        if len(rows) > 1:
            self.observer.fleet_dispatch([h for h, _ in rows])

    def _submit_timed(self, rows, fn):
        """Run one verifier submit; DCN-spanning dispatches (>1 host) are
        wall-timed into the fleet DCN-seconds counter — an upper bound on
        the cross-host collective cost (XLA doesn't expose the collective
        alone at this seam)."""
        if len(rows) <= 1:
            return fn()
        t0 = _time.monotonic()
        try:
            return fn()
        finally:
            self.observer.fleet_dcn_seconds(_time.monotonic() - t0)

    def dispatch_grouped(self, g, a_bits, b_bits):
        """Sharded root-grouped dispatch; NOT_SHARDED when ineligible."""
        n = self.size
        if n < 2 or g.pk_x.shape[0] % n:
            return NOT_SHARDED
        v, chips, rows = self._verifier("grouped", g.pk_x.shape[:2])
        self._pre_dispatch("grouped", chips, rows)
        with trace.annotation("device/mesh_grouped", chips=len(chips)), \
                device_ledger.ledger().dispatch("grouped", chips):
            return self._submit_timed(
                rows, lambda: v.submit(g, a_bits, b_bits)
            )

    def dispatch_grouped_raw(self, g, sig_raw, a_bits, b_bits):
        """Sharded root-grouped RAW dispatch (wire-byte signatures,
        on-mesh decompression); NOT_SHARDED when ineligible."""
        n = self.size
        if n < 2 or g.pk_x.shape[0] % n:
            return NOT_SHARDED
        v, chips, rows = self._verifier("grouped_raw", g.pk_x.shape[:2])
        self._pre_dispatch("grouped_raw", chips, rows)
        with trace.annotation("device/mesh_grouped_raw", chips=len(chips)), \
                device_ledger.ledger().dispatch("grouped_raw", chips):
            return self._submit_timed(
                rows, lambda: v.submit(g, sig_raw, a_bits, b_bits)
            )

    def dispatch_pk_grouped(self, g, a_bits, b_bits):
        """Sharded pk-grouped dispatch; NOT_SHARDED when ineligible."""
        n = self.size
        if n < 2 or g.msg_x.shape[0] % n:
            return NOT_SHARDED
        v, chips, rows = self._verifier("pk_grouped", g.msg_x.shape[:2])
        self._pre_dispatch("pk_grouped", chips, rows)
        with trace.annotation("device/mesh_pk_grouped", chips=len(chips)), \
                device_ledger.ledger().dispatch("pk_grouped", chips):
            return self._submit_timed(
                rows, lambda: v.submit(g, a_bits, b_bits)
            )

    def dispatch_pk_grouped_raw(self, g, sig_raw, a_bits, b_bits):
        """Sharded pk-grouped RAW dispatch (wire-byte signatures,
        on-mesh decompression); NOT_SHARDED when ineligible."""
        n = self.size
        if n < 2 or g.msg_x.shape[0] % n:
            return NOT_SHARDED
        v, chips, rows = self._verifier("pk_grouped_raw", g.msg_x.shape[:2])
        self._pre_dispatch("pk_grouped_raw", chips, rows)
        with trace.annotation("device/mesh_pk_grouped_raw", chips=len(chips)), \
                device_ledger.ledger().dispatch("pk_grouped_raw", chips):
            return self._submit_timed(
                rows, lambda: v.submit(g, sig_raw, a_bits, b_bits)
            )

    def dispatch_bisect(self, arrs, r_bits):
        """Sharded bisection-tree dispatch; NOT_SHARDED when ineligible
        (the sharded kernel needs a power-of-two batch the host already
        padded — non-pow2 buckets stay on the single-device kernel)."""
        n = self.size
        lanes = arrs.pk_x.shape[0]
        if n < 2 or lanes % n or lanes & (lanes - 1):
            return NOT_SHARDED
        v, chips, rows = self._verifier("bisect", (lanes,))
        self._pre_dispatch("bisect", chips, rows)
        with trace.annotation("device/mesh_bisect", chips=len(chips)), \
                device_ledger.ledger().dispatch("bisect", chips):
            return self._submit_timed(rows, lambda: v.submit(arrs, r_bits))

    # -- failure policy -----------------------------------------------------

    def evict(self, chip: int | None = None, reason: str = "failure"):
        """Remove a sick chip from the census and shrink the serving mesh.
        Returns the NEW serving size, or None when nothing was evicted
        (no mesh / last healthy chip / unknown chip already out)."""
        with self._lock:
            if len(self._healthy) <= 1:
                return None
            if chip is None or chip not in self._healthy:
                # no attribution: drop the highest-index healthy chip (the
                # serving prefix keeps chip 0, the root-tail owner, stable)
                chip = self._healthy[-1]
            self._healthy.remove(chip)
            self._evicted.append({"chip": chip, "reason": reason})
            new_size = self.size
        self.observer.mesh_eviction(chip, reason)
        self._publish()
        logger.warning(
            "mesh: evicted chip %d (%s) — serving continues on %d chip(s)",
            chip, reason, max(new_size, 1),
        )
        return new_size

    def evict_host(self, host: int | None = None, reason: str = "failure"):
        """The chip-eviction FSM one level up: remove a whole HOST from
        the serving census, rebalance its gossip subnets onto the
        survivors (via the attached FleetRouter) and keep serving on a
        smaller two-level mesh. Returns the NEW total serving size, or
        None when nothing was evicted (single-host census / last serving
        host / unknown host already out)."""
        with self._lock:
            gone = {e["host"] for e in self._evicted_hosts}
            active = [
                h for h in range(len(self._host_map)) if h not in gone
            ]
            if len(self._host_map) < 2 or len(active) <= 1:
                return None
            if host is None or host not in active:
                # no attribution: drop the highest-rank active host (host
                # 0, the root-tail owner of the two-level mesh, stays)
                host = active[-1]
            self._evicted_hosts.append({"host": host, "reason": reason})
            new_size = self.size
            new_hosts = self.hosts_serving
        self.observer.fleet_host_eviction(host, reason)
        moved = None
        if self._router is not None:
            try:
                moved = self._router.evict_host(host)
            except Exception:  # pragma: no cover — routing must not mask
                logger.exception("fleet: router rebalance failed")
        self._publish()
        logger.warning(
            "fleet: evicted host %d (%s) — serving continues on %d "
            "host(s) / %d chip(s)%s",
            host, reason, max(new_hosts, 1), max(new_size, 1),
            f", {moved} subnet(s) rebalanced" if moved is not None else "",
        )
        return new_size

    def readmit(self) -> int:
        """Restore every evicted chip AND host to the census (canary
        passed). Returns the number of census entries re-admitted."""
        with self._lock:
            n = len(self._evicted)
            nh = len(self._evicted_hosts)
            if not n and not nh:
                return 0
            self._healthy = list(range(len(self._devices)))
            self._evicted = []
            self._evicted_hosts = []
        if n:
            self.observer.mesh_readmission(n)
        if self._router is not None and nh:
            try:
                self._router.readmit_hosts()
            except Exception:  # pragma: no cover
                logger.exception("fleet: router readmit failed")
        self._publish()
        logger.info(
            "mesh: re-admitted %d chip(s) + %d host(s) — serving mesh "
            "back to %d", n, nh, self.size,
        )
        return n + nh

    def has_evicted(self) -> bool:
        return bool(self._evicted or self._evicted_hosts)

    # -- introspection ------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                "devices_total": len(self._devices),
                "healthy": list(self._healthy),
                "serving": self._serving_chips(),
                "size": self.size,
                "evicted": [dict(e) for e in self._evicted],
                "dispatches": self._dispatches,
                "compiled": sorted(
                    f"{k[0]}:{'x'.join(str(d) for d in k[1])}"
                    f"@{sum(len(r) for _, r in k[2])}"
                    + (f"/{len(k[2])}hosts" if len(k[2]) > 1 else "")
                    for k in self._verifiers
                ),
            }
            if len(self._host_map) > 1:
                snap["fleet"] = self._fleet_fields_locked()
            return snap

    def _fleet_fields_locked(self) -> dict:
        rows = self._serving_rows()
        return {
            "hosts_total": len(self._host_map),
            "hosts_serving": len(rows),
            "layout": {str(h): list(r) for h, r in rows},
            "evicted_hosts": [dict(e) for e in self._evicted_hosts],
            "host_dispatches": {
                str(h): n for h, n in sorted(self._host_dispatches.items())
            },
        }

    def fleet_snapshot(self) -> dict | None:
        """Host-level census for `/debug/fleet` and the bench document;
        None on a single-host census (endpoint reports wired: false)."""
        with self._lock:
            if len(self._host_map) <= 1:
                return None
            doc = self._fleet_fields_locked()
        if self._router is not None:
            try:
                doc["router"] = self._router.snapshot()
            except Exception as e:  # pragma: no cover — census must not fail
                logger.debug(f"fleet router snapshot failed: {e}")
        return doc


def auto_mesh(observer: PipelineMetrics | None = None):
    """Mesh policy at verifier construction (LODESTAR_TPU_MESH):

      auto (default)  mesh when >1 ACCELERATOR device is visible — real
                      multi-chip hardware. Virtual CPU meshes are opt-in:
                      tier-1 tests and single-chip tools run with 8
                      virtual CPU devices, and silently routing them
                      through the sharded compiles would be a massive
                      cold-cache regression for zero parallelism (the
                      "devices" share host cores).
      force / 1 / on  mesh whenever >1 device of ANY platform is visible
                      (bench's CPU-mesh phase, multi-chip drills).
      off / 0 / false never mesh.

    A fleet census rides the same policy: when ``LODESTAR_TPU_FLEET``
    is active (parallel/fleet.FleetTopology) the visible devices group
    into per-host rows — by `process_index` for a real jax.distributed
    fleet (initialized here, before device enumeration), or split into
    virtual hosts in emulation — and the dispatcher serves a two-level
    (DCN × ICI) mesh. Mesh policy gates first: a CPU fleet emulation
    still needs LODESTAR_TPU_MESH=force.

    Returns a BlsMeshDispatcher or None. A multi-device host whose mesh
    cannot be built raises: serving unsharded there would hide lost
    chips behind a node that looks healthy."""
    from ..utils.env import env_str

    mode = (env_str("LODESTAR_TPU_MESH") or "auto").strip().lower()
    if mode in ("0", "off", "false", "none"):
        return None
    from .fleet import FleetTopology

    topo = FleetTopology.from_env()
    if topo.active:
        # must precede jax.devices(): the distributed runtime is what
        # makes remote hosts' devices visible in the global census
        topo.ensure_initialized()
    import jax

    devices = jax.devices()
    if len(devices) < 2:
        return None
    if mode not in ("1", "on", "force") and devices[0].platform == "cpu":
        return None
    hosts = topo.group_devices(devices) if topo.active else None
    dispatcher = BlsMeshDispatcher(devices, observer=observer, hosts=hosts)
    if not dispatcher.enabled:
        return None
    logger.info(
        "mesh serving enabled: %d %s device(s), serving size %d "
        "across %d host(s)",
        len(devices), devices[0].platform, dispatcher.size,
        dispatcher.hosts_serving,
    )
    return dispatcher
