"""Size-bounded LRU GC for the persistent XLA compile cache + AOT store.

The `.jax_cache` directory only ever grows: every kernel revision, bench
shape and mesh size leaves its executables behind (the sharded grouped
kernel alone serializes ~7 MB per shape, and a round of warmup + bench +
mesh-scaling probes writes dozens of entries). The `.aot_store` of
serialized AOT executables (ISSUE 19) grows the same way and its
artifacts are BIGGER (~40 MB for the grouped kernel on CPU). Entries in
both are independent files — deleting one costs exactly one recompile
(or one re-export) of that kernel — so the right policy is plain LRU by
file age with ONE shared size bound across both directories, the same
shape as the reference's worker-pool keeping `poolSize` bounded rather
than unbounded.

    python tools/prune_compile_cache.py                # bound to 2 GiB
    python tools/prune_compile_cache.py --limit-gb 6   # custom bound
    python tools/prune_compile_cache.py --dry-run      # report only
    python tools/prune_compile_cache.py --no-aot       # .jax_cache only

`tools/warmup.py` invokes `prune(...)` automatically at the end of every
warm-up pass (LODESTAR_TPU_CACHE_LIMIT_GB overrides the 2 GiB default),
so the steady-state workflow — warm, bench, repeat — self-bounds instead
of filling the disk. Recency is `max(atime, mtime)`: atime tracks cache
HITS where the filesystem records it (an entry the node loads every
restart stays — `aot_store.load` additionally utimes on every hit),
mtime is the portable fallback on noatime mounts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_LIMIT_GB = 2.0
ENV_LIMIT = "LODESTAR_TPU_CACHE_LIMIT_GB"
# the cache utils/jax_env.enable_compile_cache points JAX at
DEFAULT_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or (
    os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", ".jax_cache"
    ))
)


def default_limit_gb() -> float:
    """The configured bound: LODESTAR_TPU_CACHE_LIMIT_GB, else 2 GiB."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    )
    from lodestar_tpu.utils.env import env_float

    return env_float(ENV_LIMIT)


def default_aot_dir() -> str | None:
    """The configured AOT store directory sharing the byte budget, or
    None when the store is disabled (LODESTAR_TPU_AOT_STORE=off)."""
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    )
    try:
        from lodestar_tpu.ops.aot_store import store_dir
    except ImportError:
        return None  # standalone copy outside the repo tree
    return store_dir()


def scan(cache_dir: str) -> list[tuple[float, int, str]]:
    """[(recency, size, path)] for every regular file in the cache —
    oldest first. Missing directory scans as empty (a fresh checkout has
    no cache yet; pruning it is a no-op, not an error)."""
    entries = []
    try:
        names = os.listdir(cache_dir)
    except FileNotFoundError:
        return []
    for name in names:
        path = os.path.join(cache_dir, name)
        if not os.path.isfile(path):
            continue
        st = os.stat(path)
        entries.append((max(st.st_atime, st.st_mtime), st.st_size, path))
    entries.sort()
    return entries


_AOT_AUTO = object()  # sentinel: resolve the AOT dir from the env registry


def prune(
    cache_dir: str = DEFAULT_CACHE_DIR,
    limit_gb: float | None = None,
    dry_run: bool = False,
    aot_dir=_AOT_AUTO,
) -> dict:
    """Delete least-recently-used entries until the cache fits the bound.

    The bound is SHARED across the XLA trace cache and the AOT executable
    store (ISSUE 19): both directories' entries compete in one LRU order,
    so a rarely-restarted shape's 40 MB AOT artifact is evicted before a
    hot trace-cache entry. `aot_dir` defaults to the env-configured store
    (None = cache dir only).

    Returns {"entries", "entries_remaining", "total_bytes",
    "limit_bytes", "removed", "removed_bytes", "dirs", "aot_removed"} —
    `removed` lists the pruned paths (would-be-pruned under `dry_run`).
    A real (non-dry) prune is observable: a structured
    `compile_cache_prune` log line on stderr and a `note_prune` into the
    compile ledger (metrics when a registry is live, artifact record
    always)."""
    if limit_gb is None:
        limit_gb = default_limit_gb()
    if aot_dir is _AOT_AUTO:
        aot_dir = default_aot_dir()
    dirs = [cache_dir]
    if aot_dir and os.path.abspath(aot_dir) != os.path.abspath(cache_dir):
        dirs.append(aot_dir)
    entries = []
    for d in dirs:
        entries.extend(scan(d))
    entries.sort()
    total = sum(size for _, size, _ in entries)
    limit = int(limit_gb * (1 << 30))
    removed: list[str] = []
    removed_bytes = 0
    if total > limit:
        for _, size, path in entries:
            if not dry_run:
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    continue  # concurrent writer already replaced it
            removed.append(path)
            removed_bytes += size
            total -= size
            if total <= limit:
                break
    aot_prefix = os.path.abspath(aot_dir) + os.sep if aot_dir else None
    result = {
        "entries": len(entries),
        "entries_remaining": len(entries) - len(removed),
        "total_bytes": total,
        "limit_bytes": limit,
        "removed": removed,
        "removed_bytes": removed_bytes,
        "dirs": dirs,
        "aot_removed": (
            sum(1 for p in removed
                if os.path.abspath(p).startswith(aot_prefix))
            if aot_prefix else 0
        ),
    }
    if not dry_run:
        _observe(result)
    return result


def _observe(result: dict) -> None:
    """Make the prune observable: one structured JSON log line on stderr
    (always — grep-able even when nothing else is wired), plus the
    compile ledger's `note_prune`, which persists the record into the
    next `compile_ledger.json` artifact and ticks
    `lodestar_tpu_compile_cache_pruned_bytes_total` /
    `lodestar_tpu_compile_cache_entries` on every live metrics
    pipeline."""
    print(
        json.dumps({
            "event": "compile_cache_prune",
            "entries": result["entries"],
            "entries_remaining": result["entries_remaining"],
            "removed": len(result["removed"]),
            "removed_bytes": result["removed_bytes"],
            "total_bytes": result["total_bytes"],
            "dirs": result.get("dirs"),
            "aot_removed": result.get("aot_removed", 0),
        }),
        file=sys.stderr,
        flush=True,
    )
    try:
        sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
        )
        from lodestar_tpu.observability.compile_ledger import ledger
    except ImportError:
        return  # standalone copy outside the repo tree
    ledger().note_prune(result)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cache-dir", default=DEFAULT_CACHE_DIR,
                    help="compile-cache directory (default: "
                    "JAX_COMPILATION_CACHE_DIR, else repo .jax_cache)")
    ap.add_argument("--limit-gb", type=float, default=None,
                    help=f"size bound in GiB (default: ${ENV_LIMIT} or "
                         f"{DEFAULT_LIMIT_GB})")
    ap.add_argument("--dry-run", action="store_true",
                    help="report what would be pruned without deleting")
    ap.add_argument("--aot-dir", default=None,
                    help="AOT executable store sharing the byte budget "
                         "(default: the LODESTAR_TPU_AOT_STORE dir)")
    ap.add_argument("--no-aot", action="store_true",
                    help="bound the XLA trace cache only")
    args = ap.parse_args(argv)
    limit_gb = args.limit_gb if args.limit_gb is not None else default_limit_gb()
    aot_dir = None if args.no_aot else (args.aot_dir or _AOT_AUTO)
    result = prune(args.cache_dir, limit_gb, dry_run=args.dry_run,
                   aot_dir=aot_dir)
    verb = "would prune" if args.dry_run else "pruned"
    print(
        f"cache {' + '.join(result['dirs'])}: {result['entries']} entries, "
        f"bound {limit_gb} GiB; {verb} {len(result['removed'])} "
        f"entries ({result['removed_bytes'] / (1 << 30):.2f} GiB, "
        f"{result['aot_removed']} aot) -> "
        f"{result['total_bytes'] / (1 << 30):.2f} GiB"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
