"""On-disk store of serialized AOT-compiled XLA executables.

PR 11 measured the restart problem this module exists to fix: 168.1 s
cold to serving-ready (163.4 s of it XLA compile) vs 33.7 s even with a
warm `.jax_cache` — the trace cache removes the *compile* but a restart
still pays tracing + lowering for every kernel, and a post-eviction mesh
shrink recompiles ON the serving path. This store removes XLA from the
restart loop entirely: each kernel's `Lowered.compile()` product is
serialized with `jax.experimental.serialize_executable` and persisted,
keyed by the compile ledger's existing (kernel, shape-or-static key)
signature plus a build fingerprint (jax/jaxlib/backend/device kind and
count — the PR 11 `build_info` labels), so a restarted node
`deserialize_and_load`s machine code instead of tracing anything.

Robustness is the point, not just speed:

- artifact writes are write-to-tmp + `os.replace` (atomic on POSIX), so
  a crash mid-export can never leave a half-written file under the
  final name;
- every artifact carries a JSON header with a SHA-256 of the payload;
  truncated, bit-flipped or version-mismatched artifacts raise a typed
  error (`AotMiss` / `AotCorrupt` / `AotVersionMismatch`) that the
  compile ledger turns into a counted, flight-recorded fallback to a
  normal JIT compile — never a crash, never a silently wrong
  executable;
- the payload pickle is only opened AFTER the checksum verifies: the
  checksum is an integrity (not authenticity) check — the store
  directory has the same trust level as `.jax_cache` and the code
  itself.

File layout (one file per (kernel, key, fingerprint)):

    8 bytes   magic  b"LTPUAOT1" (the trailing digit is the format
              version; a future format bump reads as version_mismatch,
              not corruption)
    4 bytes   big-endian header length
    N bytes   JSON header {kernel, key, device_ids, fingerprint{...},
              payload_sha256, payload_len, created_unix} — device_ids
              are the devices the executable was compiled for, and the
              only ones it is loaded onto
    M bytes   pickle of (serialized_executable_bytes, in_tree, out_tree)
              — the `serialize_executable.serialize` triple

The store location honors LODESTAR_TPU_AOT_STORE (0/off/none disables;
unset = `default_store_dir()`, which follows the compile cache); load and
export are independently gated by LODESTAR_TPU_AOT_LOAD (default on)
and LODESTAR_TPU_AOT_EXPORT (the producer mode `tools/warmup.py
--aot-export` sets). The compile ledger (observability/compile_ledger)
owns ALL accounting — this module never touches metrics or the flight
recorder, so it stays importable from tools without a registry.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import tempfile
import time

__all__ = [
    "AotError",
    "AotMiss",
    "AotCorrupt",
    "AotVersionMismatch",
    "AotStore",
    "fingerprint",
    "store",
    "store_dir",
    "load_enabled",
    "export_enabled",
    "reset_for_tests",
]

MAGIC = b"LTPUAOT1"
_MAGIC_STEM = MAGIC[:-1]  # any version of the format
_HEADER_LEN_MAX = 1 << 20  # a header is ~300 bytes; 1 MiB = corrupt
SUFFIX = ".aot"

# the build identity an executable is only valid under: machine code
# compiled by one jaxlib for one backend/device-set must never be loaded
# into another (runtime_info is the PR 11 build_info source)
FINGERPRINT_KEYS = ("jax", "jaxlib", "backend", "device_kind",
                    "device_count")


class AotError(Exception):
    """Base for every store failure mode the ledger degrades on."""


class AotMiss(AotError):
    """No artifact for this (kernel, key) — the normal cold case."""


class AotCorrupt(AotError):
    """Artifact exists but is truncated/bit-flipped/unreadable."""


class AotVersionMismatch(AotError):
    """Artifact is intact but for a different build (jax/jaxlib/backend/
    device set) or an older store format."""


def fingerprint() -> dict:
    """The build identity stamped into (and checked against) every
    artifact. Device enumeration is required — an executable is machine
    code for a specific device set."""
    from ..utils.jax_env import runtime_info

    info = runtime_info(enumerate_devices=True)
    return {k: str(info.get(k, "unknown")) for k in FINGERPRINT_KEYS}


def _digest(kernel: str, key: str, fp: dict) -> str:
    blob = json.dumps([kernel, key, fp], sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:24]


def _safe(name: str) -> str:
    return "".join(c if c.isalnum() or c in "-_" else "_" for c in name)


class AotStore:
    """One directory of `.aot` artifacts, addressed by (kernel, key)
    under the CURRENT build fingerprint. Thread-safe by construction:
    loads are read-only and saves are atomic renames."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self._fingerprint: dict | None = None  # resolved lazily: jax init

    def current_fingerprint(self) -> dict:
        if self._fingerprint is None:
            self._fingerprint = fingerprint()
        return self._fingerprint

    def path_for(self, kernel: str, key: str) -> str:
        digest = _digest(kernel, key, self.current_fingerprint())
        return os.path.join(self.root, f"{_safe(kernel)}-{digest}{SUFFIX}")

    # -- producer -----------------------------------------------------------

    def save(self, kernel: str, key: str, compiled) -> dict:
        """Serialize a `jax.stages.Compiled` and atomically persist it.
        Returns the written header. Raises AotError on serialization
        failure (the caller counts it and keeps serving the in-memory
        executable — export failure must never fail a dispatch)."""
        try:
            from jax.experimental import serialize_executable

            payload_bytes, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            payload = pickle.dumps(
                (payload_bytes, in_tree, out_tree),
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            # the executable's own device assignment: loading must bind
            # exactly these devices, not every local one
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
        except Exception as e:
            raise AotCorrupt(f"serialize failed: {e!r}") from e
        header = {
            "kernel": kernel,
            "key": key,
            "device_ids": device_ids,
            "fingerprint": self.current_fingerprint(),
            "payload_sha256": hashlib.sha256(payload).hexdigest(),
            "payload_len": len(payload),
            "created_unix": round(time.time(), 1),
        }
        header_bytes = json.dumps(header, sort_keys=True).encode()
        path = self.path_for(kernel, key)
        os.makedirs(self.root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=self.root, prefix=os.path.basename(path), suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(MAGIC)
                f.write(struct.pack(">I", len(header_bytes)))
                f.write(header_bytes)
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic: readers see old-or-new, never half
        except OSError as e:
            try:
                os.unlink(tmp)
            except OSError:
                pass  # tmp already renamed or never created
            raise AotCorrupt(f"artifact write failed: {e!r}") from e
        header["path"] = path
        return header

    # -- consumer -----------------------------------------------------------

    def read_header(self, path: str) -> dict:
        """Parse and validate an artifact's header WITHOUT loading the
        payload (directory listings, prune tooling). Raises the same
        typed errors as `load`."""
        try:
            with open(path, "rb") as f:
                return self._read_header_open(f)
        except FileNotFoundError:
            raise AotMiss(path) from None
        except OSError as e:
            raise AotCorrupt(f"unreadable artifact: {e!r}") from e

    def _read_header_open(self, f) -> dict:
        magic = f.read(len(MAGIC))
        if magic != MAGIC:
            if magic.startswith(_MAGIC_STEM):
                raise AotVersionMismatch(f"store format {magic!r}")
            raise AotCorrupt("bad magic")
        raw_len = f.read(4)
        if len(raw_len) != 4:
            raise AotCorrupt("truncated header length")
        (header_len,) = struct.unpack(">I", raw_len)
        if not 0 < header_len <= _HEADER_LEN_MAX:
            raise AotCorrupt(f"implausible header length {header_len}")
        header_bytes = f.read(header_len)
        if len(header_bytes) != header_len:
            raise AotCorrupt("truncated header")
        try:
            header = json.loads(header_bytes)
        except ValueError as e:
            raise AotCorrupt(f"header not JSON: {e!r}") from e
        if not isinstance(header, dict):
            raise AotCorrupt("header not an object")
        return header

    def load(self, kernel: str, key: str):
        """Load the executable for (kernel, key) under the current
        fingerprint. Returns a callable `jax.stages.Compiled`. Raises
        AotMiss / AotCorrupt / AotVersionMismatch — the ledger maps each
        to its outcome counter and falls back to JIT."""
        path = self.path_for(kernel, key)
        try:
            with open(path, "rb") as f:
                header = self._read_header_open(f)
                if header.get("fingerprint") != self.current_fingerprint():
                    raise AotVersionMismatch(
                        f"built for {header.get('fingerprint')}"
                    )
                if header.get("kernel") != kernel or header.get("key") != key:
                    # digest collision or a hand-renamed file: the header
                    # is the authority, the filename just an index
                    raise AotCorrupt("header kernel/key mismatch")
                payload = f.read()
        except FileNotFoundError:
            raise AotMiss(f"{kernel}:{key}") from None
        except OSError as e:
            raise AotCorrupt(f"unreadable artifact: {e!r}") from e
        expected_len = header.get("payload_len")
        if expected_len != len(payload):
            raise AotCorrupt(
                f"payload {len(payload)}B, header says {expected_len}B"
            )
        sha = hashlib.sha256(payload).hexdigest()
        if sha != header.get("payload_sha256"):
            raise AotCorrupt("payload checksum mismatch")
        import jax

        by_id = {d.id: d for d in jax.devices()}
        device_ids = header.get("device_ids") or []
        if not device_ids or any(i not in by_id for i in device_ids):
            raise AotVersionMismatch(
                f"built for devices {device_ids}, visible {sorted(by_id)}"
            )
        # checksum verified: the pickle below is the bytes the exporter
        # wrote, bit-for-bit
        try:
            payload_bytes, in_tree, out_tree = pickle.loads(payload)
            from jax.experimental import serialize_executable

            loaded = serialize_executable.deserialize_and_load(
                payload_bytes, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception as e:
            raise AotCorrupt(f"deserialize failed: {e!r}") from e
        try:
            os.utime(path)  # recency for the shared LRU prune budget
        except OSError:
            pass  # read-only store: LRU falls back to mtime
        return loaded

    # -- introspection ------------------------------------------------------

    def entries(self) -> list[dict]:
        """Header (+ path/bytes) of every parseable artifact; unreadable
        files are listed with an `error` field instead of raising —
        `/debug/compiles` and the pruner must see a corrupt store, not
        fail on it."""
        out = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return out
        for name in names:
            if not name.endswith(SUFFIX):
                continue
            path = os.path.join(self.root, name)
            try:
                header = self.read_header(path)
                header = {
                    k: header.get(k)
                    for k in ("kernel", "key", "fingerprint", "payload_len",
                              "created_unix")
                }
            except AotError as e:
                header = {"error": f"{type(e).__name__}: {e}"}
            try:
                header["bytes"] = os.path.getsize(path)
            except OSError:
                header["bytes"] = 0
            header["path"] = path
            out.append(header)
        return out

    def total_bytes(self) -> int:
        total = 0
        try:
            names = os.listdir(self.root)
        except OSError:
            return 0
        for name in names:
            try:
                total += os.path.getsize(os.path.join(self.root, name))
            except OSError:
                continue
        return total


def default_store_dir() -> str:
    """The store beside the compile cache: `aot_store/` inside
    `JAX_COMPILATION_CACHE_DIR` when that is set, else the repo-local
    `.aot_store` (sibling of `.jax_cache`)."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache:
        return os.path.join(os.path.abspath(cache), "aot_store")
    return os.path.abspath(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "..", "..", ".aot_store")
    )


def store_dir() -> str | None:
    """The configured store directory, or None when disabled
    (LODESTAR_TPU_AOT_STORE=0/off/none)."""
    from ..utils.env import raw

    env = raw("LODESTAR_TPU_AOT_STORE")
    if env is not None and env.strip().lower() in ("0", "off", "none", ""):
        return None
    return env or default_store_dir()


def load_enabled() -> bool:
    from ..utils.env import env_bool

    return env_bool("LODESTAR_TPU_AOT_LOAD")


def export_enabled() -> bool:
    from ..utils.env import env_bool

    return env_bool("LODESTAR_TPU_AOT_EXPORT")


_store: AotStore | None = None
_store_root: str | None = None


def store() -> AotStore | None:
    """The process-wide store for the configured directory, or None when
    disabled. Re-resolved when the env-configured root changes (tests
    point LODESTAR_TPU_AOT_STORE at tmp dirs)."""
    global _store, _store_root
    root = store_dir()
    if root is None:
        _store, _store_root = None, None
        return None
    if _store is None or _store_root != root:
        _store = AotStore(root)
        _store_root = root
    return _store


def reset_for_tests() -> None:
    """Drop the cached store instance (and its memoized fingerprint)."""
    global _store, _store_root
    _store, _store_root = None, None
