"""Traffic: slots of mainnet gossip made from a seed, one general generator.

What a slot holds comes from the deployment (`configs/<name>.json`):
committees per slot, committee size, aggregators per committee, the
attestation subnets the node subscribes to, the block's aggregates and
sync committee. How it is offered comes from the traffic mix
(`traffic/<name>.json`): which request kinds, on which lane, batchable or
not, how many blocks per call, when in the slot each kind is due, and
whether the loop is open or closed (read by drive.py).

Within a slot every validator signs what it signs on mainnet: one
attestation, and, if it aggregates, a selection proof and an
aggregate-and-proof envelope; the proposer signs the block and its RANDAO
reveal. Committee c holds the keys [c * size, (c + 1) * size), the same
in every slot, so the node's pubkey cache, filled by the warm-up slot,
holds them as it would on a node that has run for an epoch. Secret keys
are sk_i = base + i with base drawn from the seed, so the attestations of
one committee are consecutive sums of H(root) and its aggregate is
(sum of sk) * H(root). Roots and messages are new in every slot.

An aggregator's aggregate leaves out one member of its own (a distinct
one per aggregator), so no aggregate repeats another's bits, as gossip
validation requires of what reaches signature verification.

A mix may name request kinds under `invalid`: in every slot, one set of
each such kind carries the signature of its neighbour, another set's. It
is the kind's first set in arrival order in even slots, and in odd ones
its last, or, where the mix offers a slot's first `lead_sets` sets as a
batch of their own (drive.py), the last set of that lead. So it opens
the batch it rides in, then closes one: a verifier that leaves either
half of a batch unchecked passes an invalid request.

The signing runs in worker processes (`spawn`, no JAX in them) while the
parent builds the verifier stack; it imports nothing of the system under
test.
"""

from __future__ import annotations

import hashlib
import random
from dataclasses import dataclass, field

import bls12 as B

KINDS = ("block", "attestation", "aggregate")


@dataclass
class Request:
    kind: str
    lane: str
    batchable: bool
    offset_s: float  # due time within its slot (open loop)
    sets: list  # indices into Traffic.sets


@dataclass
class Traffic:
    slots: list  # one list of Request per slot, in arrival order
    # (pk48, pk affine (x, y), msg32, sig96, sk) per signature set
    sets: list = field(default_factory=list)
    invalid: set = field(default_factory=set)  # indices of invalid sets

    def expect(self, req_sets) -> bool:
        """The verdict a request of these sets must get."""
        return not any(k in self.invalid for k in req_sets)


def _h(*parts) -> bytes:
    return hashlib.sha256(":".join(str(p) for p in parts).encode()).digest()


class Plan:
    """Every number of the traffic, drawn from the seed; no signing."""

    def __init__(self, config: dict, mix: dict, seed: int, n_slots: int):
        self.cfg = config
        self.mix = mix
        self.seed = seed
        self.n_slots = n_slots
        self.committees = config["committees_per_slot"]
        self.size = config["committee_size"]
        self.aggregators = config["aggregators_per_committee"]
        self.subscribed = list(range(config["subscribed_subnets"]))
        self.n_keys = config["validators"]
        if self.n_keys != self.committees * self.size:
            raise ValueError("validators must be committees x committee size")
        if 2 * self.aggregators > self.size:
            raise ValueError("an aggregator leaves out a non-aggregator")
        self.base = int.from_bytes(_h(seed, "base"), "big") % (B.R - self.n_keys - 1) + 1
        self.block_aggregates = config["block_aggregates"]
        self.sync_size = config["sync_committee_size"]
        self.kinds = {r["kind"]: r for r in mix["requests"]}
        unknown = set(self.kinds) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown request kinds {sorted(unknown)}")
        self.invalid = mix.get("invalid", [])
        if set(self.invalid) - set(self.kinds):
            raise ValueError(f"invalid kinds {self.invalid} not in the mix")

    def sk(self, i: int) -> int:
        return (self.base + i) % B.R

    def sum_sk(self, lo: int, n: int) -> int:
        """sum of sk_i for i in [lo, lo + n)."""
        return (n * (self.base + lo) + n * (n - 1) // 2) % B.R

    def att_root(self, slot: int, c: int) -> bytes:
        return _h(self.seed, "att", slot, c)

    def tasks(self) -> list[tuple]:
        out = []
        for s in range(self.n_slots):
            if "attestation" in self.kinds or "aggregate" in self.kinds:
                out += [("committee", s, c) for c in range(self.committees)]
            if "block" in self.kinds:
                out += [("block", s, q) for q in range(4)]
        return out


def _consecutive_g2(h, k0: int, n: int, step: int = 1) -> list[bytes]:
    """Compressed (k0 + step * k) * h for k < n, step = +-1: one
    multiplication, then sums."""
    d = h if step > 0 else (h[0], B.f2_neg(h[1]))
    acc = B.g2_mul_affine(h, k0)
    pts = [acc]
    for _ in range(n - 1):
        acc = B.g2_add_affine(acc, d)
        pts.append(acc)
    return [B.g2_compress(a) for a in B.g2_normalize_many(pts)]


def _consecutive_g1(k0: int, n: int, step: int = 1) -> list[tuple]:
    """(compressed, affine) of (k0 + step * k) * g1 for k < n."""
    g = B.G1_GEN if step > 0 else B.g1_neg(B.G1_GEN)
    acc = B.g1_mul(B.G1_GEN, k0)
    pts = [acc]
    for _ in range(n - 1):
        acc = B.g1_add(acc, g)
        pts.append(acc)
    return [(B.g1_compress(a), a) for a in B.g1_affine_many(pts)]


def _pk(sk: int) -> tuple:
    return _consecutive_g1(sk, 1)[0]


def _mul_g2(h, k: int) -> bytes:
    return B.g2_compress(B.g2_normalize_many([B.g2_mul_affine(h, k)])[0])


def run_task(plan: Plan, task: tuple) -> tuple:
    """Sign one part of a slot; returns (task, {kind: sets}), each set
    (pk, pk affine, msg, sig, sk)."""
    kind, s, c = task
    out: dict = {}
    if kind == "committee":
        lo = c * plan.size
        root = plan.att_root(s, c)
        h = B.hash_to_g2(root)
        if "attestation" in plan.kinds and c in plan.subscribed:
            sigs = _consecutive_g2(h, plan.sk(lo), plan.size)
            pks = _consecutive_g1(plan.sk(lo), plan.size)
            out["attestation"] = [
                (*pks[k], root, sigs[k], plan.sk(lo + k))
                for k in range(plan.size)
            ]
        if "aggregate" in plan.kinds:
            a = plan.aggregators
            sel_root = _h(plan.seed, "selection", s)
            agg_pks = _consecutive_g1(plan.sk(lo), a)
            sel = _consecutive_g2(B.hash_to_g2(sel_root), plan.sk(lo), a)
            # aggregator j's aggregate: the committee less member a + j,
            # whose key sums to s0 - j
            s0 = (plan.sum_sk(lo, plan.size) - plan.sk(lo + a)) % B.R
            sub_sig = _consecutive_g2(h, s0, a, step=-1)
            sub_pk = _consecutive_g1(s0, a, step=-1)
            aggs = []
            for j in range(a):
                sk = plan.sk(lo + j)
                env_root = _h(plan.seed, "envelope", s, c, j)
                aggs.append((
                    (*agg_pks[j], sel_root, sel[j], sk),
                    (*agg_pks[j], env_root, B.sign(sk, env_root), sk),
                    (*sub_pk[j], root, sub_sig[j], (s0 - j) % B.R),
                ))
            out["aggregate"] = aggs
    else:  # block part q of 4
        q = c
        sets = []
        if q == 0:
            rng = random.Random(int.from_bytes(_h(plan.seed, "proposer", s), "big"))
            p = rng.randrange(plan.n_keys)
            for what in ("block", "randao"):
                # RANDAO signs the epoch: one root for every slot of a run
                root = _h(plan.seed, what, s if what == "block" else 0)
                sets.append((*_pk(plan.sk(p)), root,
                             B.sign(plan.sk(p), root), plan.sk(p)))
        # the block's aggregates: the committees of the slots before
        for k in range(q, plan.block_aggregates, 4):
            back, c2 = divmod(k, plan.committees)
            root = plan.att_root(s - 1 - back, c2)
            sk = plan.sum_sk(c2 * plan.size, plan.size)
            sets.append((*_pk(sk), root, _mul_g2(B.hash_to_g2(root), sk), sk))
        if q == 3:
            rng = random.Random(int.from_bytes(_h(plan.seed, "sync", s), "big"))
            lo = rng.randrange(plan.n_keys - plan.sync_size + 1)
            sk = plan.sum_sk(lo, plan.sync_size)
            root = _h(plan.seed, "sync", s)
            sets.append((*_pk(sk), root, _mul_g2(B.hash_to_g2(root), sk), sk))
        out["block"] = sets
    return task, out


def assemble(plan: Plan, results) -> Traffic:
    """The signed parts, put into slots of requests in arrival order: each
    kind spread evenly over its `due_s` span of the slot, in an order
    drawn from the seed (subnets interleave as gossip does)."""
    parts: dict = {}
    for (kind, s, c), out in results:
        for what, items in out.items():
            parts.setdefault((s, what), {})[(kind, c)] = items
    traffic = Traffic(slots=[[] for _ in range(plan.n_slots)])
    table = traffic.sets

    def add(items) -> list[int]:
        lo = len(table)
        table.extend(items)
        return list(range(lo, len(table)))

    lead = plan.mix.get("lead_sets")

    def spoil(ids, last: bool) -> None:
        """Give the first (or the `last`, of the lead where there is one)
        of `ids` its neighbour's signature: a valid encoding, signed by
        another key."""
        k = min(lead or len(ids), len(ids)) - 1 if last else 0
        j = k - 1 if last else 1
        pk, xy, msg, _, sk = table[ids[k]]
        table[ids[k]] = (pk, xy, msg, table[ids[j]][3], sk)
        traffic.invalid.add(ids[k])

    per_call = plan.kinds.get("block", {}).get("blocks_per_call", 1)
    for s in range(plan.n_slots):
        reqs = []
        for kind, spec in plan.kinds.items():
            got = parts.get((s, kind), {})
            if kind == "block":
                sets = [x for key in sorted(got) for x in got[key]]
                groups = [add(sets)] if sets else []
            elif kind == "attestation":
                groups = [add([x]) for key in sorted(got) for x in got[key]]
            else:
                groups = [add(list(x)) for key in sorted(got) for x in got[key]]
            rng = random.Random(int.from_bytes(_h(plan.seed, "order", s, kind), "big"))
            rng.shuffle(groups)
            if kind in plan.invalid and groups:
                spoil([i for g in groups for i in g], last=s % 2 == 1)
            lo, hi = spec["due_s"]
            for k, g in enumerate(groups):
                off = lo + (hi - lo) * k / len(groups)
                reqs.append(Request(kind, spec["lane"], spec["batchable"],
                                    off, g))
        reqs.sort(key=lambda r: r.offset_s)
        traffic.slots[s] = reqs
    if per_call > 1:  # a call carries the blocks of `per_call` slots
        for s in range(0, plan.n_slots, per_call):
            blocks = [r for t in range(s, min(s + per_call, plan.n_slots))
                      for r in traffic.slots[t] if r.kind == "block"]
            for t in range(s, min(s + per_call, plan.n_slots)):
                traffic.slots[t] = [r for r in traffic.slots[t]
                                    if r.kind != "block"]
            if blocks:
                merged = Request("block", blocks[0].lane, blocks[0].batchable,
                                 blocks[0].offset_s,
                                 [i for b in blocks for i in b.sets])
                traffic.slots[s].insert(0, merged)
    return traffic


def make(plan: Plan, submit):
    """Start signing every part of `plan` through `submit` (an executor's
    submit); returns a function that waits and assembles the traffic."""
    futures = [submit(run_task, plan, t) for t in plan.tasks()]

    def collect() -> Traffic:
        return assemble(plan, [f.result() for f in futures])

    return collect
