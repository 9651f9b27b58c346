#!/usr/bin/env python3
"""Chip smoke test: the node's BLS signature-verification path, once, on a TPU.

Builds the verifier stack the node builds
(`chain/verifier_stack.build_verifier_stack`: lane dispatcher → supervisor
→ device kernels, the native CPU tier as the supervisor's second tier),
at the node's kernel shapes and with the node's startup: every kernel
the device tier can dispatch is compiled before the stack serves. Then
it feeds the lanes mainnet-preset traffic made from `--seed` with the
native C signer over 2,048 distinct validator keys:

  gossip    one slot of unaggregated attestations for a node on all 64
            subnets at 1M validators: 64 signing roots x 512 sets =
            32,768 single-set requests on the attestation lane,
            coalesced into grouped 64x512 dispatches;
  block     one block's signature sets as one block-lane request:
            proposer, RANDAO, 128 aggregate attestations with distinct
            roots and a 512-key sync aggregate (the per-set kernel);
  tampered  both again with one signature swapped for another valid
            one: the batch fails and the per-set verdict path (the
            bisection tree and its probes) must pinpoint the bad set.

Every verdict is compared with the CPU tier (native C pairing), which
verifies each distinct set once (tiled copies are the same set). The run
fails — non-zero exit and no result line — when there is no TPU, a
verdict differs, the supervisor served anything from the CPU tier other
than its audit of device-rejected sets, the breaker is not closed,
device decompression fell back to the host, a kernel compiled on the
serving path, or a Pallas kernel would run in interpreter mode. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Usage (from the repository root):

    python chip_smoke.py [--seed S]   # one chip: the whole path
    python chip_smoke.py --chips 4    # the same stack on a 4-chip mesh:
                                      # gossip through the sharded
                                      # kernels, and sharded vs
                                      # single-device verdict bytes

The phase functions take their sizes and the stack's kernel shapes, so
they also run at a tiny size on the CPU when imported; `main` runs only
on a TPU. One process holds the
chip throughout and starts no child process.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time

# 1M validators / 32 slots / 64 committees per slot
MAINNET_COMMITTEE = 1_000_000 // 32 // 64
SYNC_COMMITTEE_SIZE = 512
BLOCK_AGGREGATES = 128  # MAX_ATTESTATIONS
N_KEYS = 2048


def log(msg: str) -> None:
    print(msg, flush=True)


# -- traffic ------------------------------------------------------------------


class Keys:
    """`n` distinct validator keys made from `seed`: sk_i = base + i, so
    pk_i = pk_{i-1} + G costs one point addition per key."""

    def __init__(self, seed: int, n: int):
        from lodestar_tpu.bls import api as bls
        from lodestar_tpu.bls.curve import PointG1
        from lodestar_tpu.bls.fields import R as CURVE_ORDER

        rng = random.Random(seed)
        base = rng.randrange(1, CURVE_ORDER - n)
        self.order = CURVE_ORDER
        self.sk = [base + i for i in range(n)]
        g = PointG1.generator()
        p = g * base
        self.pk = []
        for _ in range(n):
            self.pk.append(bls.PublicKey(p))
            p = p + g
        for pk in self.pk:
            pk.to_bytes()  # compress once, off the timed path

    def sign(self, sk: int, msg: bytes) -> bytes:
        from lodestar_tpu import native
        from lodestar_tpu.bls import api as bls

        rc, sig = native.bls_sign(
            (sk % self.order).to_bytes(32, "big"), msg, bls.DST_G2
        )
        if rc != 0:
            raise RuntimeError(f"native signer failed (rc={rc})")
        return sig

    def aggregate(self, members, msg: bytes):
        """One aggregate set: the members' pubkeys summed by the node's
        own aggregation, signed with the summed secret key (the same
        point as the sum of the members' signatures)."""
        from lodestar_tpu.bls import api as bls

        pk = bls.aggregate_pubkeys([self.pk[i] for i in members])
        sig = self.sign(sum(self.sk[i] for i in members), msg)
        return bls.SignatureSet(pubkey=pk, message=msg, signature=sig)


class Traffic:
    """`sets` in arrival order; `distinct[index[i]]` is the set `sets[i]`
    copies (each tiled copy is individually valid)."""

    def __init__(self, sets, distinct, index):
        self.sets, self.distinct, self.index = sets, distinct, index


def make_gossip(keys: Keys, seed: int, roots: int = 64, lanes: int = 512,
                signers: int = 32) -> Traffic:
    """One slot of unaggregated attestations: `roots` subnets, each root
    signed by `signers` distinct keys, tiled to `lanes` sets per root and
    interleaved across subnets in arrival order."""
    from lodestar_tpu.bls import api as bls

    rng = random.Random(seed ^ 0xA77E)
    distinct = []
    for s in range(roots):
        root = rng.randbytes(32)
        for j in range(signers):
            k = (s * signers + j) % len(keys.sk)
            distinct.append(bls.SignatureSet(
                pubkey=keys.pk[k], message=root,
                signature=keys.sign(keys.sk[k], root),
            ))
    index = [
        s * signers + (t % signers)
        for t in range(lanes) for s in range(roots)
    ]
    return Traffic([distinct[i] for i in index], distinct, index)


def make_block(keys: Keys, seed: int, aggregates: int = BLOCK_AGGREGATES,
               committee: int = MAINNET_COMMITTEE,
               sync: int = SYNC_COMMITTEE_SIZE) -> Traffic:
    """One block's signature sets: proposer, RANDAO reveal, `aggregates`
    aggregate attestations over distinct roots (`committee` members
    each) and a `sync`-key sync aggregate."""
    from lodestar_tpu.bls import api as bls

    rng = random.Random(seed ^ 0xB10C)
    n = len(keys.sk)
    proposer = rng.randrange(n)
    sets = []
    for _ in range(2):  # block signature, RANDAO reveal
        root = rng.randbytes(32)
        sets.append(bls.SignatureSet(
            pubkey=keys.pk[proposer], message=root,
            signature=keys.sign(keys.sk[proposer], root),
        ))
    for a in range(aggregates):
        start = (a * committee) % n
        members = [(start + t) % n for t in range(committee)]
        sets.append(keys.aggregate(members, rng.randbytes(32)))
    members = rng.sample(range(n), min(sync, n))
    sets.append(keys.aggregate(members, rng.randbytes(32)))
    return Traffic(sets, list(sets), list(range(len(sets))))


def tamper(traffic: Traffic, seed: int) -> tuple[Traffic, int]:
    """A copy with one set's signature swapped for another set's (a
    valid encoding that does not verify). Returns (copy, its index)."""
    from lodestar_tpu.bls import api as bls

    rng = random.Random(seed ^ 0x7A3)
    k = rng.randrange(len(traffic.sets))
    src = traffic.sets[k]
    donor = next(s for s in traffic.distinct if s.message != src.message)
    bad = bls.SignatureSet(
        pubkey=src.pubkey, message=src.message, signature=donor.signature
    )
    distinct = traffic.distinct + [bad]
    index = list(traffic.index)
    index[k] = len(distinct) - 1
    sets = list(traffic.sets)
    sets[k] = bad
    return Traffic(sets, distinct, index), k


def cpu_reference(stack, traffic: Traffic) -> list[bool]:
    """Per-set verdicts from the CPU tier, each distinct set once."""
    per_distinct = stack.cpu.verify_signature_sets_individual(
        traffic.distinct
    )
    return [bool(per_distinct[i]) for i in traffic.index]


# -- phases -------------------------------------------------------------------


def run_gossip(stack, traffic: Traffic) -> tuple[list[bool], float]:
    """Every set as its own attestation-lane request (gossip shape);
    returns per-set verdicts and the seconds from first submit to last
    verdict (a verdict is a host bool: the device work behind it has
    finished)."""
    t0 = time.perf_counter()
    pending = [
        stack.dispatcher.submit([s], lane="attestation")
        for s in traffic.sets
    ]
    verdicts = [bool(r()) for r in pending]
    return verdicts, time.perf_counter() - t0


def run_block(stack, traffic: Traffic) -> tuple[bool, float]:
    """The block's sets as one block-lane request."""
    t0 = time.perf_counter()
    verdict = bool(stack.dispatcher.submit(traffic.sets, lane="block")())
    return verdict, time.perf_counter() - t0


def run_block_verdicts(stack, traffic: Traffic) -> tuple[list[bool], float]:
    """Per-set verdicts for a failed block — the chain's pinpointing
    call (bisection tree + probes on the device)."""
    t0 = time.perf_counter()
    out = stack.dispatcher.verify_signature_sets_individual(traffic.sets)
    return [bool(v) for v in out], time.perf_counter() - t0


def compare(name: str, got: list[bool], want: list[bool]) -> list[str]:
    if got == want:
        bad = [i for i, v in enumerate(want) if not v]
        log(f"  {name}: {len(got)} verdicts equal to the CPU tier"
            + (f"; rejected set(s) {bad}" if bad else ""))
        return []
    diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    return [f"{name}: {len(diff)} verdict(s) differ from the CPU tier "
            f"(first at {diff[:5]}; lengths {len(got)}/{len(want)})"]


def counters(pipeline, stack) -> dict:
    sup = pipeline.supervisor_snapshot()
    return {
        "breaker": stack.supervisor.breaker_state,
        "breaker_state_gauge": sup["breaker_state"],
        "fallbacks": sup["fallbacks"],
        "retries": sup["retries"],
        "deadline_exceeded": sup["deadline_exceeded"],
        "both_tiers_failed": sup["both_tiers_failed"],
        "verdict_mismatches": sup["verdict_mismatches"],
        "waiter_timeouts": sup["waiter_timeouts"],
        "decompress_fallbacks": int(pipeline.decompress_fallbacks.value()),
        "bisect": pipeline.bisect_snapshot(),
        "planner": pipeline.planner_snapshot()["decisions"],
        "mesh": pipeline.mesh_snapshot(),
    }


def check_counters(c: dict) -> list[str]:
    """The supervisor served nothing from the CPU tier except its audit
    of device-rejected sets (the healthy path for invalid input), and
    no device step failed, timed out or was overturned."""
    problems = []
    tier = {k: v for k, v in c["fallbacks"].items() if k != "negative_audit"}
    if any(tier.values()):
        problems.append(f"CPU-tier fallbacks: {tier}")
    for key in ("retries", "deadline_exceeded", "both_tiers_failed",
                "verdict_mismatches", "waiter_timeouts",
                "decompress_fallbacks", "breaker_state_gauge"):
        if c[key]:
            problems.append(f"{key} = {c[key]}")
    if c["breaker"] != "closed":
        problems.append(f"breaker {c['breaker']}")
    return problems


def check_pallas() -> list[str]:
    """No Pallas kernel on the path runs in interpreter mode: the Fp
    multiply knobs route to Pallas kernels, which off a TPU would run in
    the interpreter."""
    from lodestar_tpu.ops.pallas_fp import on_tpu
    from lodestar_tpu.utils.env import env_bool

    forced = [k for k in ("LODESTAR_TPU_PALLAS_MUL", "LODESTAR_TPU_PALLAS_MXU")
              if env_bool(k)]
    interpret = not on_tpu()
    log(f"pallas: forced {forced or 'none'}, interpret={interpret}")
    if forced and interpret:
        return [f"Pallas kernels in interpreter mode: {forced}"]
    return []


def log_compiles(since: int = 0) -> int:
    """Log the compile ledger's events from index `since` on; returns
    the event count (the next call's `since`)."""
    from lodestar_tpu.observability.compile_ledger import ledger

    snap = ledger().snapshot()
    events = snap["events"]
    for e in events[since:]:
        log(f"  compile {e['kernel']:<16} {e['seconds']:>9.3f}s "
            f"cache={e['cache']} [{e['key']}]")
    log(f"  compile events {len(events) - since} "
        f"({sum(e['seconds'] for e in events[since:]):.3f}s) "
        f"cache={snap['cache']}")
    return len(events)


class Driver:
    """Builds the node's stack (`build_verifier_stack`: every kernel the
    device tier can dispatch is compiled before it serves, as at the
    node's startup) and runs phases through it, each checked against
    the CPU tier. `shapes` overrides the stack's kernel shapes (a tiny
    rehearsal on the CPU); the chip runs the node's own."""

    def __init__(self, pipeline, **shapes):
        from lodestar_tpu.chain.verifier_stack import build_verifier_stack

        self.pipeline = pipeline
        self.problems = check_pallas()
        t0 = time.perf_counter()
        self.stack = build_verifier_stack(pipeline, **shapes)
        log(f"stack: built in {time.perf_counter() - t0:.1f}s, kernels "
            f"compiled ahead {self.stack.compiled}, planner prices (s) "
            f"{self.stack.prices}")
        self.n_compiles = log_compiles()
        # time the supervisor's calls into the CPU tier (its audit of
        # device-rejected batches) from here on
        self.cpu_s = 0.0
        for name in ("verify_signature_sets",
                     "verify_signature_sets_individual"):
            setattr(self.stack.cpu, name,
                    self._timed(getattr(self.stack.cpu, name)))

    def _timed(self, fn):
        def call(sets):
            t = time.perf_counter()
            try:
                return fn(sets)
            finally:
                self.cpu_s += time.perf_counter() - t
        return call

    def reference(self, traffic: Traffic) -> list[bool]:
        cpu_s = self.cpu_s
        try:
            return cpu_reference(self.stack, traffic)
        finally:
            self.cpu_s = cpu_s  # the reference is not the supervisor's

    def phase(self, name, run, traffic, want, verdicts=lambda got: got):
        before, cpu0 = self.pipeline.stage_snapshot(), self.cpu_s
        got, dt = run(self.stack, traffic)
        self.problems.extend(compare(name, verdicts(got), want))
        after = self.pipeline.stage_snapshot()
        stages = " ".join(
            f"{k}={v['sum_s'] - before.get(k, {}).get('sum_s', 0.0):.3f}s"
            for k, v in sorted(after.items())
            if v["count"] != before.get(k, {}).get("count", 0)
        )
        log(f"  rate {name:<24} {len(traffic.sets) / dt:>12.1f} sets/s "
            f"({dt:.3f}s; stages {stages} "
            f"cpu_tier={self.cpu_s - cpu0:.3f}s)")

    def check_no_compiles(self) -> None:
        """Nothing compiled on the serving path: every kernel the phases
        dispatched was compiled by the stack build."""
        log("compiles while serving:")
        if log_compiles(self.n_compiles) != self.n_compiles:
            self.problems.append(
                "kernels compiled on the serving path (a first dispatch "
                "after the stack was built)"
            )

    def finish(self) -> list[str]:
        """Check that the counters are clean; close the stack; return
        the problems."""
        try:
            c = counters(self.pipeline, self.stack)
            log("counters: " + json.dumps(c, sort_keys=True))
            self.problems += check_counters(c)
        finally:
            self.stack.close()
        return self.problems


def run_single(pipeline, seed: int, n_keys: int = N_KEYS,
               roots: int = 64, lanes: int = 512, signers: int = 32,
               aggregates: int = BLOCK_AGGREGATES,
               committee: int = MAINNET_COMMITTEE,
               sync: int = SYNC_COMMITTEE_SIZE, **shapes) -> list[str]:
    """The one-chip path; returns the problems found (empty = pass)."""
    d = Driver(pipeline, **shapes)
    try:
        t0 = time.perf_counter()
        keys = Keys(seed, n_keys)
        gossip = make_gossip(keys, seed, roots, lanes, signers)
        block = make_block(keys, seed, aggregates, committee, sync)
        bad_gossip, k_g = tamper(gossip, seed)
        bad_block, k_b = tamper(block, seed + 1)
        log(f"traffic: {len(keys.sk)} keys, gossip {len(gossip.sets)} sets "
            f"({len(gossip.distinct)} distinct), block {len(block.sets)} "
            f"sets, tampered gossip set {k_g}, tampered block set {k_b} "
            f"({time.perf_counter() - t0:.1f}s)")
        t0 = time.perf_counter()
        want_g = d.reference(gossip)
        want_b = d.reference(block)
        want_bg = d.reference(bad_gossip)
        want_bb = d.reference(bad_block)
        log(f"cpu tier: reference verdicts in "
            f"{time.perf_counter() - t0:.1f}s")
        for rep in ("warm", "steady"):
            d.phase(f"gossip ({rep})", run_gossip, gossip, want_g)
            d.phase(f"block ({rep})", run_block, block, [all(want_b)],
                    lambda got: [got])
        d.phase("tampered gossip", run_gossip, bad_gossip, want_bg)
        d.phase("tampered block", run_block, bad_block, [all(want_bb)],
                lambda got: [got])
        d.phase("tampered block per-set", run_block_verdicts, bad_block,
                want_bb)
        d.check_no_compiles()
    finally:
        problems = d.finish()
    return problems


def mesh_parity(tpu, name: str, traffic: Traffic, want: bool) -> list[str]:
    """One marshalled batch of `traffic`, with one set of random
    coefficients, through the sharded raw, the sharded decoded and the
    single-device raw grouped kernels of `tpu` (a TpuBlsVerifier on a
    serving mesh): the verdict bytes must be identical, and `want`."""
    import numpy as np

    from lodestar_tpu.parallel.verifier import _rand_pairs, run_concurrently

    mesh = tpu._mesh
    plan = tpu._plan_groups(traffic.sets)
    g_raw, sig_raw = tpu._marshal_grouped(traffic.sets, plan, raw=True)
    g = tpu._marshal_grouped(traffic.sets, plan)
    a_bits, b_bits = _rand_pairs(g.valid.shape)
    calls = {
        "sharded_raw": lambda: mesh.dispatch_grouped_raw(
            g_raw, sig_raw, a_bits, b_bits),
        "sharded": lambda: mesh.dispatch_grouped(g, a_bits, b_bits),
        "single_raw": lambda: tpu.kernels.verify_grouped_raw(
            g_raw, sig_raw, a_bits, b_bits),
    }
    # the sharded decoded and single-device kernels are not the node's
    # mesh path, so their first calls compile: run them concurrently
    t0 = time.perf_counter()
    out = dict(zip(calls, run_concurrently(
        [lambda f=f: np.asarray(f()).tobytes() for f in calls.values()]
    )))
    log(f"  {name}: {sorted(out)} in {time.perf_counter() - t0:.1f}s")
    if len(set(out.values())) != 1:
        return [f"{name}: verdict bytes differ: {out}"]
    if bool(np.frombuffer(out["single_raw"], bool)[0]) != want:
        return [f"{name}: verdict is not {want}"]
    log(f"  {name}: verdict bytes identical across {sorted(out)}")
    return []


def run_mesh(pipeline, seed: int, chips: int = 4, n_keys: int = N_KEYS,
             roots: int = 64, lanes: int = 512, signers: int = 32,
             **shapes) -> list[str]:
    """The node's stack on a `chips`-chip mesh, at the node's kernel
    shapes: one slot of gossip and a tampered copy through the lane
    dispatcher (the sharded raw grouped kernel, and the sharded bisection
    tree for the bad set), verdicts against the CPU tier. Then one
    marshalled batch of each, with one set of random coefficients,
    through the sharded raw, the sharded decoded and the single-device
    raw grouped kernels: the verdict bytes must be identical. Every chip
    must have taken dispatches."""
    d = Driver(pipeline, **shapes)
    try:
        tpu = d.stack.device._inner
        mesh = tpu._mesh
        if mesh is None or mesh.size != chips:
            d.problems.append(
                f"serving mesh size {None if mesh is None else mesh.size}, "
                f"want {chips}"
            )
            return d.problems
        keys = Keys(seed, n_keys)
        gossip = make_gossip(keys, seed, roots, lanes, signers)
        bad, k = tamper(gossip, seed)
        log(f"traffic: {len(keys.sk)} keys, gossip {len(gossip.sets)} sets "
            f"over {roots} roots, tampered set {k}")
        want_g, want_bad = d.reference(gossip), d.reference(bad)
        d.phase("gossip", run_gossip, gossip, want_g)
        d.phase("tampered gossip", run_gossip, bad, want_bad)
        d.check_no_compiles()

        for name, traffic, want in (("valid", gossip, True),
                                    ("tampered", bad, False)):
            d.problems += mesh_parity(tpu, name, traffic, want)
        m = pipeline.mesh_snapshot()
        if m["size"] != chips:
            d.problems.append(f"lodestar_bls_mesh_size = {m['size']}")
        idle = [c for c in range(chips)
                if not m["chip_dispatches"].get(str(c))]
        if idle:
            d.problems.append(f"chips without dispatches: {idle}")
    finally:
        problems = d.finish()
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded paths on a 4-chip mesh")
    args = ap.parse_args(argv)
    try:
        from lodestar_tpu import native
    except ImportError as e:
        print(f"chip_smoke: the lodestar_tpu package is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    if not native.HAVE_NATIVE_BLS:
        print("chip_smoke: the native BLS extension is unavailable",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if d0.platform != "tpu":
        print("chip_smoke: no TPU visible to JAX", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} chip(s), --chips {args.chips}",
              file=sys.stderr)
        return 2

    from lodestar_tpu.observability.stages import PipelineMetrics

    pipeline = PipelineMetrics()
    t0 = time.perf_counter()
    if args.chips == 1:
        problems = run_single(pipeline, args.seed)
    else:
        problems = run_mesh(pipeline, args.seed, chips=args.chips)
    log(f"wall: {time.perf_counter() - t0:.1f}s")
    if problems:
        for p in problems:
            print(f"chip_smoke: FAIL {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
