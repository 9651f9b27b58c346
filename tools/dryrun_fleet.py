"""Fleet dryrun: prove the two-level (DCN x ICI) mesh is bit-identical
to the single-level mesh, and that subnet-sharded ingest partitions the
work without loss (ISSUE 20 acceptance artifact -> FLEET_r01.json).

Two phases, both on virtual CPU devices (no TPU needed — the point is
the COLLECTIVE LAYOUT and the ROUTING, not silicon):

  mesh_parity    in-process: the same grouped batches (valid + one
                 tampered lane) dispatched through a 1-host x 4-chip
                 flat mesh AND a 2-host x 2-chip two-level mesh; the
                 verdict bytes must be identical, and the fleet census
                 must attribute dispatches to both host rows.

  ingest_wiring  multi-process: two subprocesses, each acting as one
                 fleet host over its FleetRouter subnet slice of a
                 deterministic 64-subnet attestation workload (one
                 valid + one tampered set per subnet, verified with the
                 pure-CPU bls oracle). The merged verdict map must be
                 disjoint, covering, and equal to a single-host run of
                 the full workload.

Usage:
    python tools/dryrun_fleet.py [--out FLEET_r01.json]
    python tools/dryrun_fleet.py --host-rank R --hosts N   (subprocess)

The --host-rank form is the per-host worker the parent spawns; it prints
its slice verdicts as JSON on stdout and must stay jax-free (router +
CPU oracle only) so the wiring phase runs in seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

SUBNETS = 64


# -- deterministic per-subnet workload (shared by parent and workers) ------

def _subnet_sets(subnet: int):
    """One valid and one tampered signature set, derived only from the
    subnet number — every process computes the identical workload."""
    from lodestar_tpu.bls import api as bls

    sk = bls.interop_secret_key(subnet + 1)
    msg = bytes([subnet]) * 32
    good = bls.SignatureSet(
        pubkey=sk.to_public_key(), message=msg, signature=sk.sign(msg).to_bytes()
    )
    wrong = bls.interop_secret_key(997)
    bad = bls.SignatureSet(
        pubkey=sk.to_public_key(),
        message=msg,
        signature=wrong.sign(msg).to_bytes(),
    )
    return good, bad


def _host_worker(rank: int, hosts: int) -> dict:
    """One fleet host: verify only the subnets this rank owns."""
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.parallel.fleet import FleetRouter

    router = FleetRouter(hosts, rank)
    verdicts: dict[str, dict] = {}
    dispatches = 0
    for subnet in range(SUBNETS):
        if not router.owns(subnet):
            router.record_foreign(subnet)
            continue
        good, bad = _subnet_sets(subnet)
        verdicts[str(subnet)] = {
            "valid": bool(bls.verify_signature_sets([good])),
            "tampered": bool(bls.verify_signature_sets([bad])),
        }
        dispatches += 2
    return {
        "rank": rank,
        "owned": len(verdicts),
        "dispatches": dispatches,
        "foreign_dropped": router.snapshot()["foreign_dropped"],
        "verdicts": verdicts,
    }


# -- phase 1: two-level mesh verdict parity --------------------------------

def _mesh_parity() -> dict:
    from lodestar_tpu.utils.jax_env import force_platform

    force_platform("cpu", 4)

    import jax
    import numpy as np

    from lodestar_tpu.utils.jax_env import enable_compile_cache

    enable_compile_cache()

    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.parallel.fleet import FleetRouter
    from lodestar_tpu.parallel.mesh import NOT_SHARDED, BlsMeshDispatcher
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier, _rand_pairs

    host = TpuBlsVerifier(buckets=(16,), grouped_configs=((8, 4),))

    def make_sets(tamper_idx=None):
        sets = []
        for i in range(16):
            sk = bls.interop_secret_key(i + 1)
            root = b"\x42" * 32 if i < 8 else b"\x43" * 32
            signer = sk if i != tamper_idx else bls.interop_secret_key(99)
            sets.append(
                bls.SignatureSet(
                    pubkey=sk.to_public_key(),
                    message=root,
                    signature=signer.sign(root).to_bytes(),
                )
            )
        return sets

    def marshal(sets):
        plan = host._plan_groups(sets)
        g = host._marshal_grouped(sets, plan)
        assert g is not None, "grouped marshal refused the dryrun batch"
        return g

    devices = jax.devices("cpu")[:4]
    flat = BlsMeshDispatcher(devices)
    fleet = BlsMeshDispatcher(
        devices, hosts=[[0, 1], [2, 3]], router=FleetRouter(2, 0)
    )
    assert flat.size == 4 and fleet.size == 4 and fleet.hosts_serving == 2

    counter = [0]

    def rng():
        counter[0] += 1
        return (0x9E3779B97F4A7C15 * counter[0]) & ((1 << 64) - 1)

    g_good = marshal(make_sets())
    g_bad = marshal(make_sets(tamper_idx=3))
    a_bits, b_bits = _rand_pairs(g_good.valid.shape, rng)

    cases = {}
    t0 = time.monotonic()
    for label, g in (("valid", g_good), ("tampered", g_bad)):
        # the single-device truth is pinned by the asserts on the flat
        # verdicts below (valid accepts, tampered rejects) — no third
        # kernel compile; this box has one core and deep pairing
        # compiles cost minutes each
        v_flat = flat.dispatch_grouped(g, a_bits, b_bits)
        v_fleet = fleet.dispatch_grouped(g, a_bits, b_bits)
        assert v_flat is not NOT_SHARDED and v_fleet is not NOT_SHARDED
        flat_bytes = np.asarray(v_flat).tobytes().hex()
        fleet_bytes = np.asarray(v_fleet).tobytes().hex()
        cases[label] = {
            "flat_verdict": bool(v_flat),
            "fleet_verdict": bool(v_fleet),
            "flat_bytes": flat_bytes,
            "fleet_bytes": fleet_bytes,
            "bit_identical": flat_bytes == fleet_bytes,
        }
        print(f"mesh_parity[{label}]: flat={bool(v_flat)} "
              f"fleet={bool(v_fleet)} identical="
              f"{flat_bytes == fleet_bytes}", flush=True)
    elapsed = round(time.monotonic() - t0, 3)

    snap = fleet.fleet_snapshot()
    parity_ok = (
        cases["valid"]["flat_verdict"] is True
        and cases["tampered"]["flat_verdict"] is False
        and all(c["bit_identical"] for c in cases.values())
    )
    return {
        "devices": 4,
        "layouts": {"flat": "1x4 (dp)", "fleet": "2x2 (dcn,ici)"},
        "cases": cases,
        "parity_ok": parity_ok,
        "elapsed_s": elapsed,
        "fleet_census": snap,
    }


# -- phase 2: multi-process subnet-sharded ingest --------------------------

def _ingest_wiring() -> dict:
    from lodestar_tpu.bls import api as bls

    me = os.path.abspath(__file__)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(
            [sys.executable, me, "--host-rank", str(r), "--hosts", "2"],
            stdout=subprocess.PIPE,
            env=env,
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        raw, _ = p.communicate(timeout=600)
        assert p.returncode == 0, f"host worker rc={p.returncode}"
        outs.append(json.loads(raw))

    merged: dict[str, dict] = {}
    for doc in outs:
        for subnet, verdict in doc["verdicts"].items():
            assert subnet not in merged, f"subnet {subnet} owned twice"
            merged[subnet] = verdict
    assert len(merged) == SUBNETS, f"coverage hole: {len(merged)}/{SUBNETS}"

    # single-host reference: the same workload with no router filtering
    reference = {}
    for subnet in range(SUBNETS):
        good, bad = _subnet_sets(subnet)
        reference[str(subnet)] = {
            "valid": bool(bls.verify_signature_sets([good])),
            "tampered": bool(bls.verify_signature_sets([bad])),
        }
    parity_ok = merged == reference
    return {
        "hosts": 2,
        "per_host": [
            {k: doc[k] for k in
             ("rank", "owned", "dispatches", "foreign_dropped")}
            for doc in outs
        ],
        "subnets_covered": len(merged),
        "disjoint": True,  # asserted above
        "parity_ok": parity_ok,
        "all_valid_accepted": all(v["valid"] for v in merged.values()),
        "all_tampered_rejected": not any(
            v["tampered"] for v in merged.values()
        ),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="write the dryrun document here (default stdout)")
    ap.add_argument("--host-rank", type=int, default=None,
                    help="internal: run as one fleet host worker")
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--skip-mesh", action="store_true",
                    help="skip the jax mesh-parity phase (wiring only)")
    args = ap.parse_args()

    if args.host_rank is not None:
        json.dump(_host_worker(args.host_rank, args.hosts), sys.stdout)
        return 0

    doc = {"artifact": "FLEET_r01", "subnet_count": SUBNETS}
    doc["ingest_wiring"] = _ingest_wiring()
    if not args.skip_mesh:
        doc["mesh_parity"] = _mesh_parity()
    ok = doc["ingest_wiring"]["parity_ok"] and (
        args.skip_mesh or doc["mesh_parity"]["parity_ok"]
    )
    doc["fleet_parity_ok"] = ok
    text = json.dumps(doc, indent=2, sort_keys=True) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out} (fleet_parity_ok={ok})")
    else:
        sys.stdout.write(text)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
