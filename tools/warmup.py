"""Compile-cache warm-up + size-bounded GC for `.jax_cache`.

Deep pairing kernels compile in minutes (CPU backend: 7-13 min for the
sharded grouped kernel); a cold cache at the wrong moment costs a restart
its first slots — or a driver dry-run its timeout (round-4 lesson:
`MULTICHIP_r04.json` went red purely on a cold-cache compile). This tool
makes warm-up an explicit, documented step:

  python tools/warmup.py                 # production ladder, current platform
  python tools/warmup.py --dryrun        # the driver's dryrun_multichip(8)
                                         #   CPU-mesh shape (run after the
                                         #   LAST kernel change of a round)
  python tools/warmup.py --prune-gb 6    # GC the cache down to 6 GiB (LRU)
  python tools/warmup.py --aot-export    # producer mode: additionally
                                         #   serialize every compiled
                                         #   executable into the AOT store
                                         #   (restart without XLA — ISSUE 19)

Every warm-up pass ends with an automatic LRU GC of the cache (bound:
LODESTAR_TPU_CACHE_LIMIT_GB, default 2 GiB) — the policy lives in
tools/prune_compile_cache.py, which is also a standalone CLI.

The production ladder = every shape the buffered verifier can dispatch
steady-state: per-set buckets (4, 16, 64, 128) + grouped configs
(16x8, 64x64) + the pk-grouped config (128x32 — the adversarial
unique-root flood defense routes here) + the bisection-verdict tree
kernel per bucket and its fixed-shape probe kernel (the per-set verdict
path, round 6) + the standalone batched final exp + the epoch-table
gather kernel + the bench shapes when --bench is given. Device
decompression is DEFAULT-ON (round 6), so the *_raw kernel variants —
on-chip signature decode + subgroup checks — are warmed for the same
shapes by default; LODESTAR_TPU_DEVICE_DECOMPRESS=0 (or
--no-device-decompress) skips them for hosts that pin the C-tier
marshal. Reference analog: the reference avoids this class of problem
by having no compile step at all (blst is AOT); on TPU the restart
story is "run warmup.py once per binary/kernel revision"
(docs/architecture.md §compile-cache). The cache location honors
JAX_COMPILATION_CACHE_DIR (utils/jax_env.enable_compile_cache) like
node.py and bench.py: that directory when set, else the repo-local
`.jax_cache`.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from lodestar_tpu.utils.jax_env import cache_dir  # noqa: E402

CACHE_DIR = cache_dir()


def prune_cache(limit_gb: float | None = None) -> None:
    """LRU-GC the cache to the bound (tools/prune_compile_cache.py owns
    the policy; default bound 2 GiB, LODESTAR_TPU_CACHE_LIMIT_GB
    overrides). XLA cache entries are independent files — deleting one
    only costs a recompile of that one kernel."""
    import prune_compile_cache

    if limit_gb is None:
        limit_gb = prune_compile_cache.default_limit_gb()
    result = prune_compile_cache.prune(CACHE_DIR, limit_gb)
    print(f"cache: {result['entries']} entries (bound {limit_gb} GiB); "
          f"pruned {len(result['removed'])} -> "
          f"{result['total_bytes'] / (1 << 30):.2f} GiB")


def warm_production(include_bench: bool, device_decompress: bool = True) -> None:
    """Compile the production dispatch ladder on the current platform
    (TPU when available — run this at deploy; each shape is one cached
    XLA executable). `device_decompress` (default-on, matching the
    runtime default) adds the *_raw kernel variants (on-chip signature
    decode) for every shape in the ladder."""
    from lodestar_tpu.observability.compile_ledger import ledger, timeline
    from lodestar_tpu.utils.jax_env import enable_compile_cache

    enable_compile_cache()
    timeline().mark("warmup_start")
    # objectives loaded before any kernel warms: the cold-start table
    # gains an `slo_ready` column, and burn state covers the whole
    # warmup ladder (a wedged compile shows as serving_ready burning)
    from lodestar_tpu.observability import slo
    from lodestar_tpu.observability.stages import default_pipeline

    slo.install(default_pipeline())
    timeline().mark("slo_ready")
    import jax

    from __graft_entry__ import (
        _example_arrays,
        _example_grouped,
        _example_pk_grouped,
    )
    from lodestar_tpu.parallel.mesh import mesh_divisor
    from lodestar_tpu.parallel.verifier import BatchVerifier, SetArrays

    buckets = (4, 16, 64, 128) + ((4096,) if include_bench else ())
    grouped = ((16, 8), (64, 64)) + (
        ((64, 256), (64, 512), (64, 1024)) if include_bench else ()
    )
    # the pk-grouped dual-axis config: the planner's default
    # (parallel/verifier pk_grouped_configs) — an adversarial unique-root
    # flood routes production batches here, so a cold compile at that
    # moment is exactly the missed-slots failure this tool prevents
    pk_grouped = ((128, 32),)
    bv = BatchVerifier(
        buckets=buckets, grouped_configs=grouped, pk_grouped_configs=pk_grouped
    )
    for b in buckets:
        arrs = SetArrays(b)
        (arrs.pk_x, arrs.pk_y, arrs.msg_x, arrs.msg_y,
         arrs.sig_x, arrs.sig_y, r_bits, arrs.valid) = _example_arrays(b)
        arrs.n = b
        t0 = time.monotonic()
        ok = bool(bv.verify_batch(arrs, r_bits))
        print(f"per-set bucket {b}: {time.monotonic() - t0:.1f}s verdict={ok}",
              flush=True)
        t0 = time.monotonic()
        ok = bv.verify_individual(arrs)
        jax.block_until_ready(ok)
        print(f"individual bucket {b}: {time.monotonic() - t0:.1f}s", flush=True)
        # the bisection-verdict tree (the per-set verdict path's common
        # case — ONE final exp) per PRODUCTION bucket; a cold compile
        # here would hit exactly when a batch just failed and verdicts
        # are urgent. The bench-only 4096 bucket is skipped: the verdict
        # path never dispatches it (bench's bisect phase runs at 128).
        if b <= 128:
            t0 = time.monotonic()
            root_ok, _levels = bv.verify_bisect_tree(arrs, r_bits)
            jax.block_until_ready(root_ok)
            print(f"bisect tree bucket {b}: {time.monotonic() - t0:.1f}s "
                  f"root_ok={bool(root_ok)}", flush=True)
        timeline().mark(f"rung_bucket_{b}")
    # the fixed-shape bisection probe kernel (ONE compile total)
    import numpy as np
    from lodestar_tpu.ops import fp12 as _fp12
    from lodestar_tpu.parallel.verifier import PROBE_LANES

    t0 = time.monotonic()
    probe = bv.probe_nodes(np.asarray(_fp12.one((PROBE_LANES,))))
    jax.block_until_ready(probe)
    print(f"bisect probe x{PROBE_LANES}: {time.monotonic() - t0:.1f}s",
          flush=True)
    # the standalone shared-inversion batched final exp (ISSUE 14): the
    # bench floor comparison and /debug/compiles entry for the batched-FE
    # tail every verdict kernel inlines
    t0 = time.monotonic()
    fe = bv.final_exp_batch(np.asarray(_fp12.one((PROBE_LANES,))))
    jax.block_until_ready(fe)
    print(f"final exp batch x{PROBE_LANES}: {time.monotonic() - t0:.1f}s",
          flush=True)
    timeline().mark("rung_final_exp_batch")
    # the epoch-table gather kernel (ISSUE 18): one tiny compile that
    # otherwise lands on the first post-restart epoch transition
    from lodestar_tpu.parallel.epoch_table import ROW_WIDTH, EpochPubkeyTable

    table = EpochPubkeyTable(epochs=1, max_rows=8)
    table.populate(0, [(bytes([i]) * 48, np.zeros(ROW_WIDTH, np.int32))
                       for i in range(4)])
    t0 = time.monotonic()
    gathered = table.gather_device(0, np.arange(4))
    if gathered is not None:
        jax.block_until_ready(gathered)
    print(f"epoch table gather x4: {time.monotonic() - t0:.1f}s "
          f"device={gathered is not None}", flush=True)
    timeline().mark("rung_epoch_table")
    for rows, lanes in grouped:
        if device_decompress:
            g, a_bits, b_bits, sig_raw = _example_grouped(rows, lanes, raw=True)
        else:
            g, a_bits, b_bits = _example_grouped(rows, lanes)
        t0 = time.monotonic()
        ok = bool(bv.verify_grouped(g, a_bits, b_bits))
        print(f"grouped {rows}x{lanes}: {time.monotonic() - t0:.1f}s "
              f"verdict={ok}", flush=True)
        if device_decompress:
            t0 = time.monotonic()
            ok = bool(bv.verify_grouped_raw(g, sig_raw, a_bits, b_bits))
            print(f"grouped raw {rows}x{lanes}: {time.monotonic() - t0:.1f}s "
                  f"verdict={ok}", flush=True)
        timeline().mark(f"rung_grouped_{rows}x{lanes}")
    for rows, lanes in pk_grouped:
        if device_decompress:
            g, a_bits, b_bits, sig_raw = _example_pk_grouped(
                rows, lanes, raw=True
            )
        else:
            g, a_bits, b_bits = _example_pk_grouped(rows, lanes)
        t0 = time.monotonic()
        ok = bool(bv.verify_pk_grouped(g, a_bits, b_bits))
        print(f"pk-grouped {rows}x{lanes}: {time.monotonic() - t0:.1f}s "
              f"verdict={ok}", flush=True)
        if device_decompress:
            t0 = time.monotonic()
            ok = bool(bv.verify_pk_grouped_raw(g, sig_raw, a_bits, b_bits))
            print(f"pk-grouped raw {rows}x{lanes}: "
                  f"{time.monotonic() - t0:.1f}s verdict={ok}", flush=True)
        timeline().mark(f"rung_pk_grouped_{rows}x{lanes}")
    # sharded-raw ladder (ISSUE 15): with >1 visible device the mesh
    # dispatcher routes raw gossip bytes to the on-mesh decompression
    # twins by default — warm them for every production grouped shape the
    # mesh can shard (rows divisible by the mesh size), or a cold compile
    # lands on the first gossip batch after a restart
    n_mesh = mesh_divisor(len(jax.devices()))
    if device_decompress and n_mesh >= 2:
        from jax.sharding import Mesh

        from lodestar_tpu.parallel.sharded import (
            ShardedGroupedRawVerifier,
            ShardedPkGroupedRawVerifier,
        )

        mesh = Mesh(np.array(jax.devices()[:n_mesh]), axis_names=("dp",))
        sgr = ShardedGroupedRawVerifier(mesh)
        for rows, lanes in grouped:
            if rows % n_mesh:
                continue
            g, a_bits, b_bits, sig_raw = _example_grouped(rows, lanes, raw=True)
            t0 = time.monotonic()
            ok = bool(sgr.submit(g, sig_raw, a_bits, b_bits))
            print(f"sharded-raw grouped {rows}x{lanes} /{n_mesh}: "
                  f"{time.monotonic() - t0:.1f}s verdict={ok}", flush=True)
            timeline().mark(f"rung_sharded_raw_{rows}x{lanes}")
        spgr = ShardedPkGroupedRawVerifier(mesh)
        for rows, lanes in pk_grouped:
            if rows % n_mesh:
                continue
            g, a_bits, b_bits, sig_raw = _example_pk_grouped(
                rows, lanes, raw=True
            )
            t0 = time.monotonic()
            ok = bool(spgr.submit(g, sig_raw, a_bits, b_bits))
            print(f"sharded-raw pk-grouped {rows}x{lanes} /{n_mesh}: "
                  f"{time.monotonic() - t0:.1f}s verdict={ok}", flush=True)
            timeline().mark(f"rung_sharded_raw_pk_{rows}x{lanes}")
    # fleet two-level twins (ISSUE 20): when a fleet topology is active
    # (LODESTAR_TPU_FLEET), the mesh dispatcher serves from a (dcn, ici)
    # two-level shard_map — a DIFFERENT executable per host count than
    # the flat single-host twins above, recorded under the fleet_*
    # kernel names. Warm them through the dispatcher itself so the
    # compile-ledger wrap (and --aot-export, which rides the ledger's
    # AOT seam) covers exactly the production dispatch path.
    from lodestar_tpu.parallel.fleet import FleetTopology

    topo = FleetTopology.from_env()
    host_rows = topo.group_devices(jax.devices()) if topo.active else None
    if host_rows is not None:
        from lodestar_tpu.parallel.mesh import NOT_SHARDED, BlsMeshDispatcher

        disp = BlsMeshDispatcher(jax.devices(), hosts=host_rows)
        if disp.hosts_serving > 1:
            for rows, lanes in grouped:
                if rows % disp.size:
                    continue
                g, a_bits, b_bits, sig_raw = _example_grouped(
                    rows, lanes, raw=True
                )
                t0 = time.monotonic()
                ok = disp.dispatch_grouped(g, a_bits, b_bits)
                if ok is NOT_SHARDED:
                    continue
                print(f"fleet grouped {rows}x{lanes} "
                      f"/{disp.hosts_serving}h: "
                      f"{time.monotonic() - t0:.1f}s verdict={bool(ok)}",
                      flush=True)
                timeline().mark(f"rung_fleet_{rows}x{lanes}")
                if device_decompress:
                    t0 = time.monotonic()
                    ok = disp.dispatch_grouped_raw(g, sig_raw, a_bits, b_bits)
                    if ok is not NOT_SHARDED:
                        print(f"fleet grouped raw {rows}x{lanes} "
                              f"/{disp.hosts_serving}h: "
                              f"{time.monotonic() - t0:.1f}s "
                              f"verdict={bool(ok)}", flush=True)
                        timeline().mark(f"rung_fleet_raw_{rows}x{lanes}")
    # the ladder is the serving contract: every production shape compiled
    # means a node restarting against this cache is serving-ready here
    t_ready = timeline().mark_serving_ready()
    snap = ledger().snapshot()
    print(f"warmup: serving-ready at {t_ready:.1f}s since process start "
          f"({snap['cumulative_seconds']:.1f}s in compiles)",
          flush=True)
    aot = snap.get("aot") or {}
    if aot.get("store") and (aot.get("counts") or aot.get("export")):
        print(f"warmup: aot store {aot['store']}: {aot.get('counts', {})} "
              f"({aot.get('loaded_executables', 0)} executable(s) "
              f"in memory)", flush=True)
    ledger().write_artifact(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "compile_ledger.json"
    ))


def warm_dryrun(n: int) -> None:
    """Warm the exact shape the driver's multichip dry-run compiles (the
    round-4 red-signal failure mode). Must run in a fresh process that
    hasn't touched jax yet — re-exec if a backend already initialized."""
    import __graft_entry__

    t0 = time.monotonic()
    __graft_entry__.dryrun_multichip(n)
    print(f"dryrun_multichip({n}) warm in {time.monotonic() - t0:.1f}s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dryrun", action="store_true",
                    help="warm the driver's CPU-mesh dryrun shape instead")
    ap.add_argument("--devices", type=int, default=8,
                    help="mesh size for --dryrun")
    ap.add_argument("--bench", action="store_true",
                    help="also warm the bench shapes (4096-set, 64x256/512)")
    ap.add_argument("--device-decompress", action="store_true",
                    help="warm the *_raw kernels (on-chip signature decode; "
                         "DEFAULT since round 6 — kept for compatibility)")
    ap.add_argument("--no-device-decompress", action="store_true",
                    help="skip the *_raw kernels (for hosts pinning the "
                         "C-tier marshal via LODESTAR_TPU_DEVICE_DECOMPRESS=0)")
    ap.add_argument("--aot-export", action="store_true",
                    help="producer mode for the AOT executable store "
                         "(ops/aot_store.py): every ladder compile is "
                         "serialized to LODESTAR_TPU_AOT_STORE so a node "
                         "restart loads machine code instead of entering "
                         "XLA (sets LODESTAR_TPU_AOT_EXPORT=1)")
    ap.add_argument("--prune-gb", type=float, default=None,
                    help="GC the cache to this many GiB (LRU) and exit")
    args = ap.parse_args()
    if args.aot_export:
        # before any jax/ledger work: export_enabled() is read at each
        # kernel's first dispatch
        os.environ["LODESTAR_TPU_AOT_EXPORT"] = "1"
    if args.prune_gb is not None:
        prune_cache(args.prune_gb)
        return
    if args.dryrun:
        warm_dryrun(args.devices)
        prune_cache()  # self-bounding: every warm-up pass ends with GC
        return
    # mirror the runtime default: raw kernels ON unless explicitly off
    # (an explicit --device-decompress wins over the env off-switch)
    from lodestar_tpu.utils.env import env_bool

    env_off = not env_bool("LODESTAR_TPU_DEVICE_DECOMPRESS")
    device_decompress = args.device_decompress or not (
        args.no_device_decompress or env_off
    )
    warm_production(args.bench, device_decompress=device_decompress)
    prune_cache()  # self-bounding: every warm-up pass ends with GC


if __name__ == "__main__":
    main()
