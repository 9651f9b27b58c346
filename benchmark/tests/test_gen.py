"""The traffic generator at a tiny size: valid sets, what each validator
signs in a slot, determinism from the seed."""

import json
import os

import pytest

import gen
from conftest import BENCH, TINY


class Now:
    def __init__(self, v):
        self.v = v

    def result(self):
        return self.v


def make(seed, cfg=TINY, traffic="gossip_live", slots=2):
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    plan = gen.Plan(cfg, mix, seed, slots)
    return plan, gen.make(plan, lambda fn, *a: Now(fn(*a)))()


@pytest.mark.parametrize("traffic", ["gossip_live", "gossip_slots"])
def test_the_cpu_tier_accepts_exactly_the_valid_sets(traffic):
    from lodestar_tpu.bls import api
    from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier

    plan, tr = make(2**31 + 3, traffic=traffic, slots=4)
    sets = [api.SignatureSet(pubkey=api.PublicKey.from_bytes(pk),
                             message=m, signature=s)
            for pk, _, m, s, _ in tr.sets]
    got = CpuBlsVerifier().verify_signature_sets_individual(sets)
    assert [i for i, v in enumerate(got) if not v] == sorted(tr.invalid)
    # one set a slot of each kind the mix names: the first of its kind in
    # arrival order in even slots, the last in odd ones
    assert plan.invalid and "block" in plan.invalid
    for s, slot in enumerate(tr.slots):
        for kind in ("block", "attestation", "aggregate"):
            ids = [i for r in slot if r.kind == kind for i in r.sets]
            bad = [k for k, i in enumerate(ids) if i in tr.invalid]
            last = min(plan.mix.get("lead_sets") or len(ids), len(ids)) - 1
            want = [last if s % 2 else 0] if kind in plan.invalid else []
            assert bad == want, (s, kind)


def test_a_slot_holds_what_mainnet_holds():
    plan, tr = make(11)
    for slot in tr.slots:
        kinds = [r.kind for r in slot]
        assert kinds.count("block") == 1
        assert kinds.count("attestation") == 2 * TINY["committee_size"]
        assert kinds.count("aggregate") == (TINY["committees_per_slot"]
                                            * TINY["aggregators_per_committee"])
        block = next(r for r in slot if r.kind == "block")
        assert len(block.sets) == 2 + TINY["block_aggregates"] + 1
        offs = [r.offset_s for r in slot]
        assert offs == sorted(offs)
        # a validator signs one attestation; an aggregator's selection
        # proof and envelope carry its key too, nothing else does
        signers = {}
        for r in slot:
            for k in r.sets:
                signers.setdefault(tr.sets[k][0], []).append(r.kind)
        for pk, what in signers.items():
            assert what.count("attestation") <= 1
            assert what.count("aggregate") in (0, 1, 2)
        # aggregates of one committee leave out distinct members
        aggs = [tr.sets[r.sets[2]][0] for r in slot if r.kind == "aggregate"]
        assert len(set(aggs)) == len(aggs)
    msgs = [{tr.sets[k][2] for r in slot for k in r.sets
             if r.kind == "attestation"} for slot in tr.slots]
    assert not msgs[0] & msgs[1]  # roots are new every slot


def test_same_seed_same_traffic_other_seed_other():
    a = make(2**31 + 99)[1]
    b = make(2**31 + 99)[1]
    c = make(2**31 + 100)[1]
    assert a.sets == b.sets
    assert [len(s) for s in a.slots] == [len(s) for s in c.slots]
    assert a.sets != c.sets
