"""Headline benchmark: batched BLS signature-set verification throughput.

Reproduces the reference's hot workload (blst verifyMultipleSignatures via
the worker pool — beacon-node/test/perf/bls/bls.test.ts shapes, BASELINE.md
north star: >=50k signature-set verifications/sec, zero queue backlog) on
the device batch kernels.

The headline is the GROUPED kernel at the gossip shape (64 unique signing
roots per batch — committees share roots; BASELINE config #2): the batch
equation regrouped by bilinearity, per-root pubkey MSMs, ψ-split
randomness (parallel/verifier.grouped_verify_kernel). The honest
worst-case row (every root unique — range-sync-of-distinct-blocks shape)
runs the per-set kernel and is reported alongside, as are the end-to-end
wire→verdict rate and the incremental state-hashing numbers.

Harness (round-6 rewrite on `lodestar_tpu.observability.bench_emit`): every
phase runs under its own deadline (LODESTAR_TPU_BENCH_PHASE_DEADLINE
seconds, graceful skip on expiry) and the run ALWAYS ends in one JSON line
on stdout — {"metric", "value", "unit", "vs_baseline", "phases",
"stage_seconds", "planner", "partial"} — even when a phase dies or the
driver's global timeout SIGTERMs the process mid-phase (the BENCH_r05
`rc: 124, parsed: null` failure mode). The full document also goes to
bench_details.json; progress lines go to stderr.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

BASELINE_SETS_PER_SEC = 50_000.0  # BASELINE.json north_star target
UNIQUE_ROOTS = 64  # committee gossip shares signing roots (config #2 shape)
GROUPED_LANES = 256  # sets per root-row: 64×256 = 16384 sets/dispatch
WORST_CASE_BATCH = 4096
REPS = 3


def _example_grouped(rows: int, lanes: int):
    """Valid grouped arrays (shared builder — __graft_entry__)."""
    from __graft_entry__ import _example_grouped as build

    return build(rows, lanes)


def _bench_grouped(jax, lanes: int = GROUPED_LANES, utilization: bool = False):
    """Device steady-state of the grouped kernel at the gossip shape.

    With `utilization`, returns (rate, busy_fraction): busy_fraction =
    async-pipelined per-call time / block-per-call time. Async submits
    overlap dispatch with device execution, block-per-call pays the full
    host round trip each call — the ratio is the fraction of steady-state
    wall time the chip spends executing vs waiting on host/dispatch
    (1.0 = dispatch fully hidden; the VERDICT r4 utilization row)."""
    from lodestar_tpu.observability.compile_ledger import ledger
    from lodestar_tpu.parallel.verifier import grouped_verify_kernel

    g, a_bits, b_bits = _example_grouped(UNIQUE_ROOTS, lanes)
    args = [
        jax.device_put(a)
        for a in (
            g.pk_x, g.pk_y, g.msg_x, g.msg_y, g.sig_x, g.sig_y,
            a_bits, b_bits, g.valid,
        )
    ]
    jax.block_until_ready(args)
    fn = ledger().wrap(jax.jit(grouped_verify_kernel), "bench_grouped")
    ok = bool(fn(*args))  # compile + correctness gate
    assert ok, "grouped bench batch failed verification"
    t0 = time.perf_counter()
    for _ in range(REPS):
        r = fn(*args)
    r.block_until_ready()
    dt = (time.perf_counter() - t0) / REPS
    rate = UNIQUE_ROOTS * lanes / dt
    if not utilization:
        return rate
    t0 = time.perf_counter()
    for _ in range(REPS):
        fn(*args).block_until_ready()  # full host round trip per call
    dt_blocked = (time.perf_counter() - t0) / REPS
    return rate, min(1.0, dt / dt_blocked)


def _bench_worst_case(jax) -> dict:
    """The adversarial row (VERDICT r4 #2):

    - `worst_case_unique`: an attacker floods unique AttestationData
      (roots never group) but signs with boundedly many keys — the
      planner routes the PK-GROUPED kernel (bilinearity on the pubkey
      axis: e(pk, Σ r_i·H_i); parallel/verifier
      pk_grouped_verify_kernel). 128 keys × 32 unique roots each.

    The distinct-pk-and-msg floor row moved to the parity-gated
    `floor_fused_pairing` phase (ISSUE 14; renamed by ISSUE 18)."""
    from __graft_entry__ import _example_pk_grouped
    from lodestar_tpu.observability.compile_ledger import ledger
    from lodestar_tpu.parallel.verifier import pk_grouped_verify_kernel

    g, a_bits, b_bits = _example_pk_grouped(128, 32, unique_msgs=8)
    args = [
        jax.device_put(x)
        for x in (g.pk_x, g.pk_y, g.msg_x, g.msg_y, g.sig_x, g.sig_y,
                  a_bits, b_bits, g.valid)
    ]
    jax.block_until_ready(args)
    fn = ledger().wrap(jax.jit(pk_grouped_verify_kernel), "bench_pk_grouped")
    ok = bool(fn(*args))
    assert ok, "pk-grouped bench batch failed verification"
    t0 = time.perf_counter()
    for _ in range(REPS):
        r = fn(*args)
    r.block_until_ready()
    dt = (time.perf_counter() - t0) / REPS
    return {
        "device_sets_per_sec_worst_case_unique": round(WORST_CASE_BATCH / dt, 2),
        "worst_case_unique_via": "pk_grouped_128x32",
    }


def _bench_floor_fused_pairing(jax) -> dict:
    """The unconditional floor, parity-gated old-vs-new (ISSUE 14,
    renamed from `floor_batched_fe` by ISSUE 18 — the floor row key is
    unchanged, so bench_compare's base-name match carries the trend).

    Shape: distinct pubkeys AND roots simultaneously (range-sync of
    distinct proposers' blocks — not an adversary-scalable shape);
    nothing groups, so the per-set kernel's rate is the floor.

    Rows:
    - `device_sets_per_sec_floor_distinct_pk_and_msg` — the REQUIRED
      floor key (binding moved here from `worst_case`), measured on the
      production per-set kernel, whose verdict tail now runs the
      shared-inversion batched final exp.
    - `device_sets_per_sec_verdicts_batched_fe` / `_legacy_fe` — the
      per-set VERDICT kernel (N per-lane final exps before ISSUE 14)
      both ways on the same device arrays. The two verdict vectors must
      be bit-identical and all-true or the phase dies: a batched-FE
      kernel that is fast but wrong must never report a floor number.
    """
    from __graft_entry__ import _example_arrays
    from lodestar_tpu.observability.compile_ledger import ledger
    from lodestar_tpu.parallel.verifier import (
        batch_verify_kernel,
        individual_verify_kernel,
        individual_verify_kernel_legacy_fe,
    )

    args = [jax.device_put(a) for a in _example_arrays(WORST_CASE_BATCH)]
    jax.block_until_ready(args)
    # verdict kernels take no r_bits (index 6): (pk, msg, sig, valid)
    v_args = args[:6] + [args[7]]

    def steady(fn, call_args):
        t0 = time.perf_counter()
        for _ in range(REPS):
            r = fn(*call_args)
        r.block_until_ready()
        return (time.perf_counter() - t0) / REPS

    new_fn = ledger().wrap(
        jax.jit(individual_verify_kernel), "bench_verdicts_batched_fe"
    )
    old_fn = ledger().wrap(
        jax.jit(individual_verify_kernel_legacy_fe), "bench_verdicts_legacy_fe"
    )
    new_v = np.asarray(new_fn(*v_args))
    old_v = np.asarray(old_fn(*v_args))
    # the parity gate: same verdicts, and the known-valid batch passes
    assert (new_v == old_v).all() and new_v.all(), (
        "floor_fused_pairing parity gate failed: batched-FE verdicts "
        "diverge from per-lane FE"
    )
    rows = {
        "device_sets_per_sec_verdicts_batched_fe": round(
            WORST_CASE_BATCH / steady(new_fn, v_args), 2
        ),
        "device_sets_per_sec_verdicts_legacy_fe": round(
            WORST_CASE_BATCH / steady(old_fn, v_args), 2
        ),
        "parity_batched_vs_legacy_fe": True,
    }

    fn = ledger().wrap(jax.jit(batch_verify_kernel), "bench_batch")
    ok = bool(fn(*args))
    assert ok, "per-set bench batch failed verification"
    rows["device_sets_per_sec_floor_distinct_pk_and_msg"] = round(
        WORST_CASE_BATCH / steady(fn, args), 2
    )
    return rows


def _bench_e2e() -> dict | None:
    """Wire-bytes → verified/s through TpuBlsVerifier (marshal included).

    Sets are pre-generated OUTSIDE the timed region (network receive is
    not the thing under test); pubkeys come from a trusted cache exactly
    like the reference's pubkey cache (worker.ts deserializes without
    re-validating). Messages share UNIQUE_ROOTS signing roots per batch —
    the real gossip shape — so the verifier routes the grouped kernel.

    Round 6: `e2e_wire_to_verdict_sets_per_sec` is the NO-FLAGS DEFAULT
    configuration — which now means device-side signature decompression
    (flipped default, VERDICT r5 #4). The host-marshal path keeps its own
    key (`e2e_host_marshal_sets_per_sec`, the rounds-1..5-comparable
    trend line), so tools/bench_compare.py never silently compares
    different configurations.

    PIPELINED: batches go through `verify_signature_sets_submit`, so the
    host marshals batch k+1 while the device verifies batch k (the
    double-buffering of VERDICT r3 #4). A marshal-only rate is reported
    alongside: on this 1-core box the host is the e2e ceiling — the
    device needs ceil(marshal_ms/device_ms) cores to saturate.
    """
    from lodestar_tpu import native
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier, _rand_pairs

    if not native.HAVE_NATIVE_BLS:
        return None

    batch = UNIQUE_ROOTS * GROUPED_LANES  # reuse the headline kernel compile
    n_keys = 64
    sks = [bls.interop_secret_key(i) for i in range(n_keys)]
    pks = [sk.to_public_key() for sk in sks]
    roots = [bytes([r]) * 32 for r in range(UNIQUE_ROOTS)]
    sig_cache: dict[tuple[int, int], bytes] = {}
    sets = []
    for i in range(batch):
        k = i % n_keys
        m = (i * 7) % UNIQUE_ROOTS
        sig = sig_cache.get((k, m))
        if sig is None:
            sig = sig_cache[(k, m)] = sks[k].sign(roots[m]).to_bytes()
        sets.append(
            bls.SignatureSet(pubkey=pks[k], message=roots[m], signature=sig)
        )

    def timed_e2e(verifier):
        ok = verifier.verify_signature_sets(sets)  # compile + warm caches
        assert ok, "e2e batch failed verification"
        verifier._h2c_cache.clear()  # first timed rep pays the unique hashes
        verifier._pk_cache.clear()  # …and the cold pubkey decompressions
        t0 = time.perf_counter()
        pending = None
        for _ in range(REPS):
            nxt = verifier.verify_signature_sets_submit(sets)
            if pending is not None:
                assert pending()
            pending = nxt
        assert pending()
        return (time.perf_counter() - t0) / REPS

    # the NO-FLAGS default configuration: device decompress is default-on
    # since round 6, so this IS the wire-to-verdict path a stock node runs
    verifier = TpuBlsVerifier(
        buckets=(batch,), grouped_configs=((UNIQUE_ROOTS, GROUPED_LANES),)
    )
    dt = timed_e2e(verifier)

    # host-marshal variant: signatures decode + subgroup-check in the C
    # tier (the rounds-1..5 default) — kept as its own comparable row
    rows = {}
    try:
        host_verifier = TpuBlsVerifier(
            buckets=(batch,),
            grouped_configs=((UNIQUE_ROOTS, GROUPED_LANES),),
            device_decompress=False,
        )
        dt_host = timed_e2e(host_verifier)
        rows["e2e_host_marshal_sets_per_sec"] = round(batch / dt_host, 2)
    except Exception as e:
        print(f"host-marshal e2e failed: {e}", file=sys.stderr)
        dt_host = None

    plan = verifier._plan_groups(sets)
    verifier._h2c_cache.clear()
    verifier._pk_cache.clear()
    t0 = time.perf_counter()
    g = verifier._marshal_grouped(sets, plan)
    _rand_pairs(g.valid.shape)
    marshal_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    g = verifier._marshal_grouped(sets, plan)
    _rand_pairs(g.valid.shape)
    marshal_warm_s = time.perf_counter() - t0
    best = min(d for d in (dt, dt_host) if d is not None)
    return {
        "e2e_wire_to_verdict_sets_per_sec": round(batch / dt, 2),
        "e2e_best_sets_per_sec": round(batch / best, 2),
        "e2e_device_decompress_sets_per_sec": round(batch / dt, 2),
        **rows,
        "marshal_sets_per_sec_warm_1core": round(batch / marshal_warm_s, 2),
        "marshal_sets_per_sec_cold_1core": round(batch / marshal_cold_s, 2),
    }


def _bench_attestation_epoch_warm() -> dict | None:
    """Epoch-cold vs epoch-warm attestation-lane HOST marshal (ISSUE 18).

    The steady-state attestation shape: distinct attesters (distinct
    pubkeys), a few shared signing roots per slot. What the epoch table
    + H(msg) dedup change is the HOST side of the lane — pubkey limbs
    and H(m) — so that is what this phase times, per rep:

    - cold: `_pk_cache`/`_h2c_cache` cleared and no epoch table entry —
      every set pays a C-tier G1 decompression and every unique root a
      hash_to_g2 (the post-restart / post-rotation first dispatch).
    - warm: table populated at "epoch transition" + roots pre-warmed via
      `warm_h2c` (the dispatcher's dedup seam); `_pk_cache` is still
      cleared per rep, so the warm rate measures the TABLE serving the
      marshal, not the bounded FIFO.

    Parity gate: the limb arrays the kernels would receive must be
    bit-identical cold vs warm — a table row may not differ from a fresh
    decompression in any bit. Acceptance: warm ≥ 2x cold.
    """
    from lodestar_tpu import native
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    if not native.HAVE_NATIVE_BLS:
        return None

    n_sets, n_roots = 256, 8
    sks = [bls.interop_secret_key(i) for i in range(n_sets)]
    pks = [sk.to_public_key() for sk in sks]
    roots = [bytes([0x18, r]) + b"\x00" * 30 for r in range(n_roots)]
    sets = [
        bls.SignatureSet(
            pubkey=pks[i],
            message=roots[i % n_roots],
            signature=sks[i].sign(roots[i % n_roots]).to_bytes(),
        )
        for i in range(n_sets)
    ]
    pk_bytes = [p.to_bytes() for p in pks]

    def marshal_once(v):
        """One attestation-lane host marshal: pubkey limbs + H(m)."""
        rows = v._pk_rows(sets)
        assert rows is not None
        for r in roots:
            assert v._hash_root(r) is not None
        return rows

    v = TpuBlsVerifier(buckets=(4,))
    t_cold = 0.0
    for _ in range(REPS):
        v._pk_cache.clear()
        with v._h2c_lock:
            v._h2c_cache.clear()
        if v._epoch_table is not None:
            v._epoch_table._entries.clear()
        t0 = time.perf_counter()
        cold_rows = marshal_once(v)
        t_cold += time.perf_counter() - t0
    cold_rate = n_sets / (t_cold / REPS)

    # epoch transition: populate the table + dedup pre-warm the roots
    v.epoch_table_populate(0, pk_bytes)
    v.warm_h2c(roots)
    t_warm = 0.0
    for _ in range(REPS):
        v._pk_cache.clear()  # the table, not the FIFO, must serve
        t0 = time.perf_counter()
        warm_rows = marshal_once(v)
        t_warm += time.perf_counter() - t0
    warm_rate = n_sets / (t_warm / REPS)

    assert np.array_equal(cold_rows[0], warm_rows[0]) and np.array_equal(
        cold_rows[1], warm_rows[1]
    ), ("attestation_epoch_warm parity gate failed: table rows diverge "
        "from fresh decompression")

    return {
        "attestation_epoch_warm_sets_per_sec": round(warm_rate, 2),
        "attestation_epoch_cold_sets_per_sec": round(cold_rate, 2),
        "attestation_epoch_warm_speedup": round(warm_rate / cold_rate, 2),
        "parity_epoch_warm_vs_cold": True,
        "attestation_epoch_warm_via": (
            f"pk_rows+h2c marshal, {n_sets} sets x {n_roots} roots"
        ),
        "epoch_table": (
            v.epoch_table_snapshot() if v._epoch_table is not None else None
        ),
    }


def _bench_adversarial_mix(jax) -> float | None:
    """50% unique-root sets injected into the gossip shape (VERDICT r3
    #1). Round 5: roots don't group across the mix, but the whole batch
    groups on the DUAL axis — the 64 signer keys — so the planner runs
    ONE pk-grouped dispatch and the attacker's unique AttestationData
    costs nothing extra (earlier rounds peeled shared roots onto the
    grouped kernel and paid the per-set kernel for the singleton half —
    the trend line changes meaning here). Device-rate row (marshal
    outside the timed region)."""
    from lodestar_tpu.parallel.verifier import (
        TpuBlsVerifier,
        _rand_pairs,
    )
    from lodestar_tpu import native
    from lodestar_tpu.bls import api as bls

    if not native.HAVE_NATIVE_BLS:
        return None

    half = WORST_CASE_BATCH // 2
    n_keys = 64
    sks = [bls.interop_secret_key(i) for i in range(n_keys)]
    pks = [sk.to_public_key() for sk in sks]
    shared_roots = [bytes([r]) + b"\x01" * 31 for r in range(UNIQUE_ROOTS)]
    sig_cache: dict[tuple[int, int], bytes] = {}
    sets = []
    for i in range(half):  # honest committee traffic
        k, m = i % n_keys, (i * 7) % UNIQUE_ROOTS
        sig = sig_cache.get((k, m))
        if sig is None:
            sig = sig_cache[(k, m)] = sks[k].sign(shared_roots[m]).to_bytes()
        sets.append(
            bls.SignatureSet(
                pubkey=pks[k], message=shared_roots[m], signature=sig
            )
        )
    for i in range(half):  # attacker-minted unique AttestationData
        k = i % n_keys
        msg = i.to_bytes(4, "big") + b"\xAD" * 28
        sets.append(
            bls.SignatureSet(
                pubkey=pks[k], message=msg, signature=sks[k].sign(msg).to_bytes()
            )
        )

    # device_decompress=False: this phase times the LIMB pk-grouped kernel
    # (marshal sits outside the timed region), so the submit gate must
    # compile that same kernel, not the raw variant the runtime default
    # would route (one compile, not two — compile containment)
    verifier = TpuBlsVerifier(
        buckets=(half,), grouped_configs=((UNIQUE_ROOTS, half // UNIQUE_ROOTS),),
        device_decompress=False,
    )
    resolver = verifier.verify_signature_sets_submit(sets)  # compile + gate
    assert resolver(), "adversarial-mix batch failed verification"

    # device-rate: marshal once, dispatch repeatedly. Roots don't group
    # (half are attacker-minted uniques), but the WHOLE batch groups on
    # the dual axis — 64 signer keys — so the planner runs ONE
    # pk-grouped dispatch (round-5 dual-axis defense): the attacker's
    # unique AttestationData costs nothing extra at all.
    pk_plan = verifier._plan_pk_groups(sets)
    assert pk_plan is not None, "mix batch must pk-group (64 keys)"
    gp = verifier._marshal_pk_grouped(sets, pk_plan)
    a2, b2 = _rand_pairs(gp.valid.shape)
    t0 = time.perf_counter()
    for _ in range(REPS):
        ok = bool(verifier.kernels.verify_pk_grouped(gp, a2, b2))
    dt = (time.perf_counter() - t0) / REPS
    assert ok
    return WORST_CASE_BATCH / dt


def _bench_bisect(pipeline) -> dict | None:
    """Bisection-verdict rows (round-6 tentpole acceptance): the
    all-valid per-set verdict path must cost ONE final exponentiation
    (bisection counter = 0 rounds), a k-invalid adversarial mix must
    isolate offenders in O(log N) rounds, and every verdict must match
    the CPU oracle bit-for-bit."""
    from lodestar_tpu import native
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier

    if not native.HAVE_NATIVE_BLS:
        return None

    n = 128  # the production per-set bucket (warmup ladder shape)
    sks = [bls.interop_secret_key(i) for i in range(n)]
    sets = []
    for i in range(n):
        msg = i.to_bytes(4, "big") + b"\xB1" * 28  # all-distinct roots
        sets.append(
            bls.SignatureSet(
                pubkey=sks[i].to_public_key(),
                message=msg,
                signature=sks[i].sign(msg).to_bytes(),
            )
        )
    verifier = TpuBlsVerifier(buckets=(n,), observer=pipeline)
    oracle = CpuBlsVerifier()

    def snap():
        return pipeline.bisect_snapshot()

    base = snap()
    out = verifier.verify_signature_sets_individual(sets)  # compile + gate
    assert out == [True] * n, "all-valid bisect batch failed"
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = verifier.verify_signature_sets_individual(sets)
    dt = (time.perf_counter() - t0) / REPS
    after_valid = snap()
    rows = {
        "bisect_all_valid_sets_per_sec": round(n / dt, 2),
        "bisect_rounds_all_valid": after_valid["rounds"] - base["rounds"],
    }

    # k-invalid adversarial mix: 3 tampered sets scattered in the batch
    wrong = bls.interop_secret_key(999)
    bad = (7, 64, 127)
    for i in bad:
        sets[i] = bls.SignatureSet(
            pubkey=sets[i].pubkey,
            message=sets[i].message,
            signature=wrong.sign(sets[i].message).to_bytes(),
        )
    pre = snap()
    t0 = time.perf_counter()
    out = verifier.verify_signature_sets_individual(sets)
    dt_bad = time.perf_counter() - t0
    post = snap()
    expect = [i not in bad for i in range(n)]
    oracle_out = oracle.verify_signature_sets_individual(sets)
    rows.update({
        "bisect_k_invalid_sets_per_sec": round(n / dt_bad, 2),
        "bisect_rounds_k_invalid": post["rounds"] - pre["rounds"],
        "bisect_probes_k_invalid": post["probes"] - pre["probes"],
        "bisect_verdicts_match_oracle": int(
            out == expect and out == oracle_out
        ),
    })
    return rows


def _bench_sharded_grouped(jax, pipeline) -> dict | None:
    """Mesh-native serving (round-7 tentpole): the grouped kernel through
    the PRODUCTION mesh dispatcher (`parallel/mesh.BlsMeshDispatcher`) on
    whatever mesh this host offers — real chips on a multi-chip slice, 8
    virtual CPU devices otherwise (main() forces the host-platform count,
    so the shape matches the driver's `dryrun_multichip(8)` warm cache:
    8·n root-rows × 64 lanes).

    Two gates before the timed reps: the sharded verdict must equal the
    single-device kernel's on the SAME arrays — once valid, once with a
    tampered signature limb — i.e. meshing changes throughput, never
    verdicts. The dispatcher ticks the lodestar_bls_mesh_* families, so
    the emitted `mesh` section carries the per-chip dispatch counts."""
    from lodestar_tpu.observability.compile_ledger import ledger
    from lodestar_tpu.parallel.mesh import NOT_SHARDED, BlsMeshDispatcher
    from lodestar_tpu.parallel.sharded import mesh_divisor
    from lodestar_tpu.parallel.verifier import grouped_verify_kernel

    devices = jax.devices()
    n = mesh_divisor(len(devices))
    if n < 2:
        return None  # single chip, no virtual mesh — nothing to shard

    rows, lanes = 8 * n, 64
    g, a_bits, b_bits = _example_grouped(rows, lanes)
    dispatcher = BlsMeshDispatcher(devices[:n], observer=pipeline)
    unsharded_fn = ledger().wrap(jax.jit(grouped_verify_kernel), "bench_grouped")

    def unsharded() -> bool:
        return bool(
            unsharded_fn(
                g.pk_x, g.pk_y, g.msg_x, g.msg_y, g.sig_x, g.sig_y,
                a_bits, b_bits, g.valid,
            )
        )

    def sharded() -> bool:
        r = dispatcher.dispatch_grouped(g, a_bits, b_bits)
        assert r is not NOT_SHARDED, "mesh dispatcher refused the bench batch"
        return bool(r)

    ok = sharded()  # compile + parity gate (valid batch)
    assert ok == unsharded() and ok, "sharded verdict diverged on valid batch"
    g.sig_x[0, 0, 0, 0] ^= 1  # tampered: both tiers must reject identically
    assert sharded() == unsharded() == False, \
        "sharded verdict diverged on tampered batch"
    g.sig_x[0, 0, 0, 0] ^= 1

    t0 = time.perf_counter()
    for _ in range(REPS):
        r = dispatcher.dispatch_grouped(g, a_bits, b_bits)
    ok = bool(r)
    dt = (time.perf_counter() - t0) / REPS
    assert ok, "sharded bench batch failed verification"
    return {
        "sharded_grouped_sets_per_sec": round(rows * lanes / dt, 2),
        "mesh_devices": n,
        "mesh_platform": devices[0].platform,
        "sharded_verdicts_match_unsharded": 1,
    }


def _bench_fleet_dryrun(jax, pipeline) -> dict | None:
    """Two-level fleet serving dryrun (ISSUE 20): the SAME grouped batch
    through the flat single-host mesh AND an emulated 2-host (dcn, ici)
    two-level mesh over the identical device set. Parity gates first —
    valid and tampered verdict bytes must be identical between the two
    layouts (`fleet_parity_ok`, gated by tools/bench_compare.py) — then
    the retained-throughput fraction `fleet_overlap_fraction` =
    t_flat / t_two_level: the cost of routing the one Fp12 partial and
    the 64 combined plane sums per host across the DCN axis instead of
    keeping every collective on ICI. 1.0 = the two-level layout serves
    at flat-mesh speed (perfect overlap); the fleet-math section of
    BASELINE.md scales host count by this fraction."""
    import numpy as np

    from lodestar_tpu.parallel.fleet import FleetRouter
    from lodestar_tpu.parallel.mesh import NOT_SHARDED, BlsMeshDispatcher
    from lodestar_tpu.parallel.sharded import mesh_divisor

    devices = jax.devices()
    n = mesh_divisor(len(devices))
    if n < 4:
        return None  # an emulated 2-host fleet needs >=2 chips per host
    rows, lanes = 8 * n, 64
    g, a_bits, b_bits = _example_grouped(rows, lanes)
    flat = BlsMeshDispatcher(devices[:n], observer=pipeline)
    half = n // 2
    fleet = BlsMeshDispatcher(
        devices[:n],
        observer=pipeline,
        hosts=[list(range(half)), list(range(half, n))],
        router=FleetRouter(2, 0, observer=pipeline),
    )

    def run(d):
        r = d.dispatch_grouped(g, a_bits, b_bits)
        assert r is not NOT_SHARDED, "fleet dryrun batch refused"
        return r

    v_flat, v_fleet = run(flat), run(fleet)
    parity = (
        np.asarray(v_flat).tobytes() == np.asarray(v_fleet).tobytes()
        and bool(v_flat)
    )
    g.sig_x[0, 0, 0, 0] ^= 1  # tampered: both layouts must reject
    vb_flat, vb_fleet = run(flat), run(fleet)
    parity = (
        parity
        and np.asarray(vb_flat).tobytes() == np.asarray(vb_fleet).tobytes()
        and not bool(vb_flat)
    )
    g.sig_x[0, 0, 0, 0] ^= 1

    def time_reps(d) -> float:
        r = None
        t0 = time.perf_counter()
        for _ in range(REPS):
            r = run(d)
        bool(r)
        return (time.perf_counter() - t0) / REPS

    t_flat, t_fleet = time_reps(flat), time_reps(fleet)
    snap = fleet.fleet_snapshot() or {}
    return {
        "fleet_parity_ok": int(parity),
        "fleet_overlap_fraction": (
            round(t_flat / t_fleet, 4) if t_fleet > 0 else 0.0
        ),
        "fleet_sets_per_sec": round(rows * lanes / t_fleet, 2),
        "fleet_hosts": fleet.hosts_serving,
        "fleet_chips_per_host": half,
        "fleet_host_dispatches": snap.get("host_dispatches", {}),
    }


def _bench_e2e_mesh_raw(jax, pipeline, headline_rate) -> dict | None:
    """Wire-bytes → verdict through the MESH raw path (ISSUE 15 tentpole):
    the no-flags default facade with a mesh attached — host marshal is a
    pure byte scatter (signatures stay compressed wire bytes), each chip
    decompresses its own row slice on device via the sharded `*_raw`
    twins, then the usual grouped pairing check.

    Parity gate before the timed reps, same contract as
    `_bench_sharded_grouped`: on ONE marshalled batch with ONE set of
    random coefficients, the sharded-raw verdict must equal the
    single-device raw kernel's — once valid, once with a tampered
    signature byte. Then the timed region is the production facade
    (`verify_signature_sets_submit`, pipelined), so the row is honestly
    wire→verdict: plan + scatter + mesh dispatch every rep.

    `e2e_mesh_raw_vs_device_headline` is the acceptance ratio: the mesh
    path must hold ≥0.7× the single-device headline on this host."""
    from lodestar_tpu import native
    from lodestar_tpu.bls import api as bls
    from lodestar_tpu.parallel.mesh import NOT_SHARDED, BlsMeshDispatcher
    from lodestar_tpu.parallel.sharded import mesh_divisor
    from lodestar_tpu.parallel.verifier import TpuBlsVerifier, _rand_pairs

    if not native.HAVE_NATIVE_BLS:
        return None
    devices = jax.devices()
    n = mesh_divisor(len(devices))
    if n < 2:
        return None  # single chip — no mesh ingest path to measure

    rows_, lanes = UNIQUE_ROOTS, 64  # the 64x64 warmup-rung shape; 64 % n == 0
    batch = rows_ * lanes
    n_keys = 64
    sks = [bls.interop_secret_key(i) for i in range(n_keys)]
    pks = [sk.to_public_key() for sk in sks]
    roots = [bytes([r]) * 32 for r in range(rows_)]
    sig_cache: dict[tuple[int, int], bytes] = {}
    sets = []
    for i in range(batch):
        k, m = i % n_keys, (i * 7) % rows_
        sig = sig_cache.get((k, m))
        if sig is None:
            sig = sig_cache[(k, m)] = sks[k].sign(roots[m]).to_bytes()
        sets.append(
            bls.SignatureSet(pubkey=pks[k], message=roots[m], signature=sig)
        )

    dispatcher = BlsMeshDispatcher(devices[:n], observer=pipeline)
    verifier = TpuBlsVerifier(
        buckets=(batch,), grouped_configs=((rows_, lanes),), mesh=dispatcher
    )
    if not verifier._device_decompress:
        return None  # DEVICE_DECOMPRESS=0 host: no raw ingest to bench

    plan = verifier._plan_groups(sets)
    assert plan is not None, "e2e mesh batch must group (64 shared roots)"
    marshalled = verifier._marshal_grouped(sets, plan, raw=True)
    assert marshalled is not None, "native tier refused the raw marshal"
    g, sig_raw = marshalled
    a_bits, b_bits = _rand_pairs(g.valid.shape)
    r = dispatcher.dispatch_grouped_raw(g, sig_raw, a_bits, b_bits)
    assert r is not NOT_SHARDED, "mesh dispatcher refused the e2e raw batch"
    ok = bool(r)
    assert ok == bool(
        verifier.kernels.verify_grouped_raw(g, sig_raw, a_bits, b_bits)
    ) and ok, "sharded-raw verdict diverged on valid batch"
    sig_raw[0, 0, 10] ^= 1  # tampered wire byte: identical rejection
    assert bool(
        dispatcher.dispatch_grouped_raw(g, sig_raw, a_bits, b_bits)
    ) == bool(
        verifier.kernels.verify_grouped_raw(g, sig_raw, a_bits, b_bits)
    ) == False, "sharded-raw verdict diverged on tampered batch"
    sig_raw[0, 0, 10] ^= 1

    ok = verifier.verify_signature_sets(sets)  # compile + correctness gate
    assert ok, "e2e mesh batch failed verification"
    verifier._h2c_cache.clear()  # first timed rep pays the unique hashes
    verifier._pk_cache.clear()
    t0 = time.perf_counter()
    pending = None
    for _ in range(REPS):
        nxt = verifier.verify_signature_sets_submit(sets)
        if pending is not None:
            assert pending()
        pending = nxt
    assert pending()
    dt = (time.perf_counter() - t0) / REPS
    rate = batch / dt
    out = {
        "e2e_mesh_raw_sets_per_sec": round(rate, 2),
        "e2e_mesh_raw_devices": n,
        "e2e_mesh_raw_verdicts_match_unsharded": 1,
    }
    if headline_rate:
        out["e2e_mesh_raw_vs_device_headline"] = round(rate / headline_rate, 4)
    return out


def _bench_flood(pipeline) -> dict:
    """Gossip-flood drill through the lane dispatcher (ISSUE 15): 16
    attester threads hammer 1-set requests with tiny lane caps while a
    proposer thread submits a 2-set block every 25 ms. The dispatcher is
    backed by a FIXED-SERVICE-TIME mock (no crypto) so the numbers
    isolate the SCHEDULING policy: the block lane must hold its latency
    (p50/p99 rows) and shed NOTHING while attestations shed freely."""
    import threading

    from lodestar_tpu.chain.bls_verifier import BlsShedError, MockBlsVerifier
    from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher

    service_s = 0.004

    class _FixedService(MockBlsVerifier):
        def verify_signature_sets(self, sets) -> bool:
            time.sleep(service_s)
            return super().verify_signature_sets(sets)

    d = BlsLaneDispatcher(
        _FixedService(), max_sigs=64, max_wait_ms=4, pipeline=pipeline,
        workers=2, max_coalesce=256, pending_cap=8,
        lane_caps={"attestation": 4, "aggregate": 4, "sync_committee": 4},
        waiter_timeout_s=30.0,
    )
    stop_at = time.perf_counter() + 2.0
    counts = {"att_ok": 0, "att_shed": 0}
    lock = threading.Lock()
    block_lat: list[float] = []

    def attester():
        while time.perf_counter() < stop_at:
            try:
                d.verify_signature_sets(["att"], lane="attestation")
                with lock:
                    counts["att_ok"] += 1
            except BlsShedError:
                with lock:
                    counts["att_shed"] += 1

    def proposer():
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            ok = d.verify_signature_sets(["blk", "blk"], lane="block")
            block_lat.append(time.perf_counter() - t0)
            assert ok, "block verify failed under flood"
            time.sleep(0.025)

    threads = [threading.Thread(target=attester, daemon=True) for _ in range(16)]
    threads.append(threading.Thread(target=proposer, daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    d.close()

    snap = pipeline.lanes_snapshot()
    lat = np.asarray(block_lat)
    rows = {
        "flood_block_verify_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "flood_block_verify_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "flood_block_requests": len(block_lat),
        "flood_block_sheds": snap["sheds"].get("block", 0),
        "flood_attestation_verified": counts["att_ok"],
        "flood_attestation_sheds": counts["att_shed"],
        "flood_overlap_fraction": snap["overlap_fraction"],
        "flood_service_time_ms": service_s * 1e3,
    }
    # the acceptance shape: blocks NEVER shed, attestations DID (the
    # caps are sized so an un-prioritized dispatcher could not pass)
    assert rows["flood_block_sheds"] == 0, "a block was shed under flood"
    assert counts["att_shed"] > 0, "flood never saturated the lane caps"
    assert rows["flood_block_verify_p99_ms"] < 500.0, (
        "block lane failed to hold latency under flood"
    )
    return rows


def _bench_hasher() -> dict:
    """Incremental state hashing at mainnet registry scale (CPU tier)."""
    from lodestar_tpu.ssz.hashing import mix_in_length
    from lodestar_tpu.ssz.tree_cache import ChunkTree
    from lodestar_tpu.state_transition.hasher import _u64_chunks

    n = 1_000_000
    rng = np.random.default_rng(1)
    balances = rng.integers(
        31_000_000_000, 33_000_000_000, size=n, dtype=np.uint64
    )
    t = ChunkTree((1 << 40) // 4)
    t0 = time.perf_counter()
    t.update(_u64_chunks(balances))
    r0 = mix_in_length(t.root(), n)
    full_s = time.perf_counter() - t0
    balances[n // 2] += 1
    t0 = time.perf_counter()
    t.update(_u64_chunks(balances))
    r1 = mix_in_length(t.root(), n)
    one_ms = (time.perf_counter() - t0) * 1e3
    assert r1 != r0
    return {
        "hasher_1m_balances_full_s": round(full_s, 3),
        "hasher_1m_one_change_ms": round(one_ms, 2),
    }


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)

    # device metrics come from the accelerator only: no accelerator, no
    # result (checked before the emitter exists, so nothing is printed)
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu":
        sys.exit("bench: no accelerator visible to JAX (platform cpu); "
                 "device metrics are not measured on the CPU")

    from lodestar_tpu.observability import BenchEmitter
    from lodestar_tpu.observability.stages import default_pipeline
    from lodestar_tpu.utils.env import env_float

    # per-phase budget: SIGALRM raises inside the phase at the deadline,
    # which is recorded as `status: timeout` and skipped — later phases
    # still run, and the final JSON always prints (emitter atexit/SIGTERM)
    deadline = env_float("LODESTAR_TPU_BENCH_PHASE_DEADLINE")
    # the watchdog THREAD emits + exits even when the main thread is stuck
    # in a C call (XLA compile) that SIGALRM/SIGTERM cannot interrupt; set
    # it below the driver's global timeout
    global_deadline = env_float("LODESTAR_TPU_BENCH_GLOBAL_DEADLINE")
    pipeline = default_pipeline()
    em = BenchEmitter(
        "bls_signature_sets_verified_per_sec",
        "sets/s",
        baseline=BASELINE_SETS_PER_SEC,
        details_path=os.path.join(here, "bench_details.json"),
        global_deadline_s=global_deadline,
    )
    # emit-time sections: a mid-run kill still reports everything the
    # pipeline observed up to the signal
    em.add_section("stage_seconds", pipeline.stage_snapshot)
    em.add_section("planner", pipeline.planner_snapshot)
    em.add_section("bisect", pipeline.bisect_snapshot)
    # failure-policy / fault counters (round 7): a round that ran with
    # CPU fallbacks, an open breaker, or an armed fault plan carries
    # supervisor.degraded=true — tools/bench_compare.py skips it so a
    # degraded round can't masquerade as a device-perf regression
    em.add_section("supervisor", pipeline.supervisor_snapshot)
    # mesh serving counters (round 7): mesh size / evictions / per-chip
    # dispatch counts — the sharded_grouped phase drives these
    em.add_section("mesh", pipeline.mesh_snapshot)
    # lane dispatcher state (ISSUE 15): queue depths / sheds / coalescing
    # — the flood phase drives these; None until a dispatcher binds
    em.add_section("lanes", pipeline.lanes_snapshot)
    # fleet counters (ISSUE 20): host census / evictions / rebalances /
    # DCN collective seconds — the fleet_dryrun phase drives these
    em.add_section("fleet", pipeline.fleet_snapshot)
    # compile accounting + cold-start timeline: which kernels compiled
    # this run, cache hit/miss, cumulative compile seconds, and the
    # process-start→serving-ready phase marks
    from lodestar_tpu.observability.compile_ledger import ledger, timeline

    em.add_section("compile_ledger", lambda: ledger().snapshot())
    em.add_section("startup", lambda: timeline().snapshot())
    # SLO verdicts (round 16): every emission carries the burn state of
    # the committed objectives — bench_compare gates on a burning one by
    # NAME instead of a raw-number diff
    from lodestar_tpu.observability import device_ledger, slo

    slo.install(pipeline)
    em.add_section("slo", slo.snapshot_or_none)
    # device-time & memory ledger (round 16): busy/idle/overlap seconds
    # by lane x kernel x chip + memory watermarks; read at emit time, so
    # the watchdog's rc=124 document shows what the chips were doing
    em.add_section("device", device_ledger.ledger().snapshot)
    # per-run artifact, written inside emit() so even the watchdog's
    # os._exit(124) path leaves compile_ledger.json behind
    em.on_emit.append(
        lambda doc: ledger().write_artifact(
            os.path.join(here, "compile_ledger.json")
        )
    )
    em.extra["config"] = {
        "grouped_batch": UNIQUE_ROOTS * GROUPED_LANES,
        "unique_roots_per_batch": UNIQUE_ROOTS,
        "worst_case_batch": WORST_CASE_BATCH,
        "phase_deadline_s": deadline,
    }

    # persistent compile cache: JAX_COMPILATION_CACHE_DIR when set, else
    # the repo-local .jax_cache — a warmup.py pass before the run makes
    # every phase hit cached executables instead of cold compiles
    from lodestar_tpu.utils.jax_env import enable_compile_cache, runtime_info

    enable_compile_cache()
    timeline().mark("devices_ready")
    em.extra["runtime_info"] = runtime_info()

    grouped_rate = None

    def saw_rate(rate: float) -> None:
        nonlocal grouped_rate
        grouped_rate = max(grouped_rate or 0.0, rate)
        em.set_headline(grouped_rate)

    _log("bench: grouped phase...")
    with em.phase("grouped_64x256", deadline_s=deadline) as ph:
        rate = _bench_grouped(jax)
        ph.record("device_sets_per_sec", round(rate, 2))
        saw_rate(rate)
        _log(f"bench: grouped {rate:.1f} sets/s")
    # first production-shape phase done == this process could serve; the
    # mark is the bench's serving-ready SLO sample (cold vs warm cache)
    t_ready = timeline().mark_serving_ready()
    # cold-start rows (ISSUE 19): serving_ready_seconds is a GATED
    # bench_compare key (time direction: growth fails the round), and the
    # per-round AOT store outcomes say WHY it moved — a round that loaded
    # executables from disk vs one that compiled reads differently here
    with em.phase("cold_start") as ph:
        aot_counts = ledger().snapshot()["aot"]["counts"]
        ph.record("serving_ready_seconds", round(t_ready, 3))
        ph.record("aot_hits", aot_counts.get("hit", 0))
        ph.record("aot_misses", aot_counts.get("miss", 0))
        ph.record("aot_exports", aot_counts.get("export", 0))
        ph.record("aot_rejected", aot_counts.get("corrupt", 0)
                  + aot_counts.get("version_mismatch", 0))
    # wider lane buckets amortize the 2R+64-Miller fixed cost further;
    # the HEADLINE takes the best shape, but each shape's rate is
    # recorded under its own phase (no cross-shape mislabeling)
    with em.phase("grouped_64x512", deadline_s=deadline) as ph:
        rate, util = _bench_grouped(jax, 512, utilization=True)
        ph.record("device_sets_per_sec", round(rate, 2))
        ph.record("device_busy_fraction", round(util, 4))
        pipeline.device_busy.set(round(util, 4))
        saw_rate(rate)
        _log(f"bench: grouped 64x512 {rate:.1f} sets/s (busy {util:.3f})")
    with em.phase("grouped_64x1024", deadline_s=deadline) as ph:
        rate = _bench_grouped(jax, 1024)
        ph.record("device_sets_per_sec", round(rate, 2))
        saw_rate(rate)
        _log(f"bench: grouped 64x1024 {rate:.1f} sets/s")

    _log("bench: worst-case phase...")
    with em.phase("worst_case", deadline_s=deadline) as ph:
        ph.update(_bench_worst_case(jax))

    _log("bench: floor fused-pairing phase...")
    with em.phase("floor_fused_pairing", deadline_s=deadline) as ph:
        ph.update(_bench_floor_fused_pairing(jax))

    _log("bench: adversarial-mix phase...")
    with em.phase("adversarial_mix_50pct", deadline_s=deadline) as ph:
        mix_rate = _bench_adversarial_mix(jax)
        if mix_rate is not None:
            ph.record("device_sets_per_sec", round(mix_rate, 2))

    _log("bench: bisect-verdicts phase...")
    with em.phase("bisect_verdicts", deadline_s=deadline) as ph:
        bisect_rows = _bench_bisect(pipeline)
        if bisect_rows is not None:
            ph.update(bisect_rows)

    _log("bench: e2e phase...")
    with em.phase("e2e", deadline_s=deadline) as ph:
        e2e_rows = _bench_e2e() or {}
        ph.update(e2e_rows)
        if "e2e_best_sets_per_sec" in e2e_rows:
            # promoted top-level key (ADVICE round 5): best-of-variants
            # e2e rate, separate from the round-4-comparable headline
            em.extra["e2e_best_sets_per_sec"] = e2e_rows["e2e_best_sets_per_sec"]

    _log("bench: attestation epoch-warm phase...")
    with em.phase("attestation_epoch_warm", deadline_s=deadline) as ph:
        epoch_rows = _bench_attestation_epoch_warm()
        if epoch_rows is not None:
            ph.update(epoch_rows)
            _log(
                "bench: attestation epoch-warm "
                f"{epoch_rows['attestation_epoch_warm_sets_per_sec']:.1f} "
                f"sets/s ({epoch_rows['attestation_epoch_warm_speedup']:.1f}x "
                "over cold)"
            )

    _log("bench: sharded-grouped phase...")
    with em.phase("sharded_grouped", deadline_s=deadline) as ph:
        sharded_rows = _bench_sharded_grouped(jax, pipeline)
        if sharded_rows is not None:
            ph.update(sharded_rows)
            _log(
                "bench: sharded grouped "
                f"{sharded_rows['sharded_grouped_sets_per_sec']:.1f} sets/s "
                f"on {sharded_rows['mesh_devices']} device(s)"
            )

    _log("bench: fleet-dryrun phase...")
    with em.phase("fleet_dryrun", deadline_s=deadline) as ph:
        fleet_rows = _bench_fleet_dryrun(jax, pipeline)
        if fleet_rows is not None:
            ph.update(fleet_rows)
            _log(
                "bench: fleet dryrun parity_ok="
                f"{fleet_rows['fleet_parity_ok']} overlap="
                f"{fleet_rows['fleet_overlap_fraction']:.3f} "
                f"({fleet_rows['fleet_sets_per_sec']:.1f} sets/s on "
                f"{fleet_rows['fleet_hosts']} emulated host(s))"
            )

    _log("bench: e2e mesh-raw phase...")
    with em.phase("e2e_mesh_raw", deadline_s=deadline) as ph:
        mesh_e2e_rows = _bench_e2e_mesh_raw(jax, pipeline, grouped_rate)
        if mesh_e2e_rows is not None:
            ph.update(mesh_e2e_rows)
            _log(
                "bench: e2e mesh-raw "
                f"{mesh_e2e_rows['e2e_mesh_raw_sets_per_sec']:.1f} sets/s "
                f"on {mesh_e2e_rows['e2e_mesh_raw_devices']} device(s)"
            )

    _log("bench: flood phase...")
    with em.phase("flood", deadline_s=deadline) as ph:
        ph.update(_bench_flood(pipeline))

    with em.phase("hasher", deadline_s=deadline) as ph:
        ph.update(_bench_hasher())

    doc = em.emit()
    if doc is not None:
        _log(f"bench details: {json.dumps(doc)[:2000]}")


if __name__ == "__main__":
    main()
