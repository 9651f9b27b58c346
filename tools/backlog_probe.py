"""Zero-backlog proof: BASELINE config #2 on the device verifier.

Synthetic gossip load — 256 attestations/slot across 64 committees (each
committee shares one signing root: the real gossip shape), delivered in
three bursts per 12 s slot like live attestation traffic (slot start,
slot/3 attestation deadline, slot*2/3 aggregates) — driven through the
production `BufferedVerifier` → `DeviceBlsVerifier` path for >= 10 slots
on the real chip.

Records per-slot buffer depth samples (lodestar_bls_verifier_buffer_sigs),
buffer-wait / sets-per-job histograms, and verdicts, and writes
backlog_run.json next to bench_details.json (VERDICT r2 next-step #5;
reference: lodestar_bls_thread_pool dashboard + gossip queue budget
"keep job wait < 3 s", network/gossip/handlers/index.ts).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()

SLOTS = 10
SLOT_SEC = float(os.environ.get("BACKLOG_SLOT_SEC", "12"))
ATT_PER_SLOT = 256
COMMITTEES = 64


def build_slot_sets(slot: int, sks, pks):
    """256 attestation signature sets over 64 shared committee roots."""
    from lodestar_tpu.bls import api as bls

    sets = []
    sigs = {}
    for i in range(ATT_PER_SLOT):
        committee = i % COMMITTEES
        k = i % len(sks)
        root = bytes([slot % 256, committee]) + b"\x5a" * 30
        sig = sigs.get((k, root))
        if sig is None:
            sig = sigs[(k, root)] = sks[k].sign(root).to_bytes()
        sets.append(
            bls.SignatureSet(pubkey=pks[k], message=root, signature=sig)
        )
    return sets


async def run() -> dict:
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.chain.bls_verifier import BufferedVerifier, DeviceBlsVerifier
    from lodestar_tpu.metrics.beacon import create_beacon_metrics

    n_keys = 64
    sks = [bls.interop_secret_key(i) for i in range(n_keys)]
    pks = [sk.to_public_key() for sk in sks]

    prom = create_beacon_metrics()
    # one flat bucket + one grouped config: every merged batch pads to 128
    # lanes, so warm-up needs exactly two compiles
    device = DeviceBlsVerifier(buckets=(128,), grouped_configs=((64, 64),))
    verifier = BufferedVerifier(device, prom=prom)

    # warm every bucket the merged batches can land in, outside the timed
    # window (a cold first dispatch would otherwise look like minutes of
    # backlog — compiles are one-time and cached)
    warm = build_slot_sets(255, sks, pks)
    t0 = time.monotonic()
    ok = verifier.verifier.verify_signature_sets(warm[:128])
    assert ok, "warm-up grouped-128 failed"
    print(f"warm grouped-128: {time.monotonic() - t0:.1f}s", flush=True)
    # the 128-set warm above routes GROUPED (64 shared roots); also warm
    # the FLAT 128 bucket with an all-unique batch
    from lodestar_tpu.bls import api as _bls

    uniq = []
    for i in range(128):
        root = bytes([i, 0xEE]) + b"\x11" * 30
        sk = sks[i % len(sks)]
        uniq.append(
            _bls.SignatureSet(
                pubkey=pks[i % len(pks)], message=root,
                signature=sk.sign(root).to_bytes(),
            )
        )
    t0 = time.monotonic()
    ok = verifier.verifier.verify_signature_sets(uniq)
    assert ok, "warm-up flat-128 failed"
    print(f"warm flat-128: {time.monotonic() - t0:.1f}s", flush=True)

    depth_samples: list[int] = []
    slot_rows = []
    all_ok = True

    async def sample_depth(stop):
        while not stop.is_set():
            buffered = sum(len(s) for s, _, _ in verifier._buffer)
            depth_samples.append(buffered)
            await asyncio.sleep(0.05)

    t_run0 = time.monotonic()
    stop = asyncio.Event()
    sampler = asyncio.create_task(sample_depth(stop))
    for slot in range(SLOTS):
        slot_t0 = time.monotonic()
        sets = build_slot_sets(slot, sks, pks)
        verdicts = []
        # three bursts per slot: singles at t0, the attestation-deadline
        # wave at slot/3, aggregates at 2/3 (handlers verify PER OBJECT —
        # one set each, batchable — exactly the gossip validation shape)
        bursts = [
            sets[: ATT_PER_SLOT // 2],
            sets[ATT_PER_SLOT // 2 : 3 * ATT_PER_SLOT // 4],
            sets[3 * ATT_PER_SLOT // 4 :],
        ]
        for b_i, burst in enumerate(bursts):
            target = slot_t0 + b_i * SLOT_SEC / 3
            delay = target - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks = [
                asyncio.create_task(verifier.verify([s], batchable=True))
                for s in burst
            ]
            verdicts.extend(await asyncio.gather(*tasks))
        all_ok = all_ok and all(verdicts)
        spent = time.monotonic() - slot_t0
        if spent < SLOT_SEC:
            await asyncio.sleep(SLOT_SEC - spent)
        window = depth_samples[-int(SLOT_SEC / 0.05) :]
        window_sorted = sorted(window)
        slot_rows.append(
            {
                "slot": slot,
                "verified": len(verdicts),
                "all_valid": all(verdicts),
                "depth_p50": window_sorted[len(window_sorted) // 2],
                "depth_p95": window_sorted[int(len(window_sorted) * 0.95)],
                "depth_max": max(window),
            }
        )
        print(f"slot {slot}: {slot_rows[-1]}", flush=True)
    stop.set()
    await sampler

    ds = sorted(depth_samples)
    return {
        "config": "BASELINE #2: 256 attestations/slot x 64 committees",
        "slots": SLOTS,
        "slot_seconds": SLOT_SEC,
        "sets_verified": verifier.metrics["sigs_verified"],
        "device_dispatches": verifier.metrics["batches"],
        "batch_fallbacks": verifier.metrics["batch_fallbacks"],
        "all_verdicts_valid": all_ok,
        "buffer_depth_p50": ds[len(ds) // 2],
        "buffer_depth_p95": ds[int(len(ds) * 0.95)],
        "buffer_depth_max": ds[-1],
        "wall_seconds": round(time.monotonic() - t_run0, 1),
        "per_slot": slot_rows,
    }


def main():
    out = asyncio.run(run())
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "backlog_run.json"
    )
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in out.items() if k != "per_slot"}))


if __name__ == "__main__":
    main()
