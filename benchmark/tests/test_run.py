"""Whole runs at a tiny size on the CPU, the chip's look skipped: the
node's lane dispatcher and supervisor over a stand-in device tier (the
system's CPU tier with a short delay). A sound run is correct; each
fault planted under the timed path makes `correct` false; the system's
own fault path (`testing.faults` flaky verdicts, the control) does too;
and a cell whose traffic is new files runs with no edit."""

import importlib.util
import json
import os
import shutil
import time

import pytest

import run
from conftest import BENCH, ROOT, TINY


class Device:
    """Stand-in device tier: the CPU tier, `flip` valid batches answered False,
    and the system's flaky-verdict hook on every batch verdict; with
    `half`, only the first or the second half of each batch is checked,
    or, given `lane_sets`, of each batch whose sets are all in it."""

    def __init__(self, flip=0, half=None, lane_sets=None, delay=0.02):
        from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier

        self.cpu = CpuBlsVerifier()
        self.delay = delay
        self.flip = flip
        self.half = half
        self.lane_sets = lane_sets

    def verify_signature_sets(self, sets):
        from lodestar_tpu.testing import faults

        time.sleep(self.delay)
        skip = self.lane_sets is None or all(id(s) in self.lane_sets
                                              for s in sets)
        if self.half is not None and len(sets) > 1 and skip:
            n = len(sets) // 2
            sets = sets[:n] if self.half == 0 else sets[n:]
        verdict = self.cpu.verify_signature_sets(sets)
        if self.flip and verdict:
            self.flip -= 1
            verdict = False
        return faults.flaky_verdict(verdict)

    def verify_signature_sets_individual(self, sets):
        return self.cpu.verify_signature_sets_individual(sets)


def builder(device, wrap=None):
    def build(pipeline):
        from lodestar_tpu.chain.bls_verifier import CpuBlsVerifier
        from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher
        from lodestar_tpu.chain.supervisor import SupervisedBlsVerifier
        from lodestar_tpu.chain.verifier_stack import VerifierStack

        cpu = CpuBlsVerifier()
        sup = SupervisedBlsVerifier(device, cpu, observer=pipeline)
        disp = BlsLaneDispatcher(sup, pipeline=pipeline)
        if wrap is not None:
            wrap(disp)
        return VerifierStack(disp, sup, device, cpu, {}, [])
    return build


def fast(mix):
    """The mix at a sixth of mainnet's slot time."""
    if mix["loop"] == "closed":
        return dict(mix, slots=3)
    return dict(mix, slot_s=2.0, requests=[
        dict(r, due_s=[x / 6 for x in r["due_s"]]) for r in mix["requests"]])


def go(name, build, seconds=3.0, mod=run):
    _, _, mix = mod.cell(name)
    return mod.run_cell(name, 2**31 + 1234, seconds, False, build=build,
                        config=TINY, mix=fast(mix), workers=2)


CELLS = ["supernode_64sub_1m.gossip_slots", "homestaker_2sub_1m.gossip_live"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = go(name, builder(Device()))
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["metrics"]["sets_per_s"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_a_device_verdict_altered_is_caught(name):
    res = go(name, builder(Device(flip=1)))
    assert not res["correct"]
    assert res["compared"]["device_mismatch"]["value"] >= 1


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("half", [0, 1])
def test_half_of_each_batch_left_out_is_caught(name, half):
    res = go(name, builder(Device(half=half)), seconds=5.0)
    assert not res["correct"]
    assert res["compared"]["wrong_verdicts"]["value"] >= 1


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("half", [0, 1])
def test_half_of_each_lane_batch_left_out_is_caught(name, half):
    """Only the batches the lane dispatcher coalesces skip half; blocks
    are checked whole, so the invalid gossip has to catch it."""
    lane_sets = set()

    def wrap(disp):
        dispatch = disp._dispatch_batch

        def marked(entries, *args):
            lane_sets.update(id(s) for e in entries for s in e[0])
            return dispatch(entries, *args)
        disp._dispatch_batch = marked

    res = go(name, builder(Device(half=half, lane_sets=lane_sets), wrap),
             seconds=5.0)
    assert not res["correct"]
    assert res["compared"]["wrong_verdicts"]["value"] >= 1


def test_a_verdict_is_timed_by_its_own_batch():
    """Two batches on the dispatcher's two workers, the later one done
    first: its request is stamped when its own batch hands it over, not
    when the earlier request of its lane is read."""
    from types import SimpleNamespace

    from drive import Driver
    from lodestar_tpu.chain.bls_verifier import BlsShedError
    from lodestar_tpu.chain.dispatcher import BlsLaneDispatcher
    from lodestar_tpu.observability.stages import PipelineMetrics

    class Verifier:
        def verify_signature_sets(self, sets):
            time.sleep(1.0 if sets[0] == "slow" else 0.05)
            return True

        def verify_signature_sets_individual(self, sets):
            return [True] * len(sets)

    pipeline = PipelineMetrics()
    disp = BlsLaneDispatcher(Verifier(), pipeline=pipeline, workers=2,
                             max_sigs=1)
    drv = Driver(disp, pipeline, ["slow", "fast"], BlsShedError)

    def req(k):
        return SimpleNamespace(kind="attestation", lane="attestation",
                               batchable=True, sets=[k])
    try:
        a = drv.offer(req(0), time.monotonic())
        time.sleep(0.2)  # a worker has taken the slow batch
        b = drv.offer(req(1), time.monotonic())
        drv.wait_done([a, b], time.monotonic() + 10.0)
    finally:
        disp.close()
        drv.close()
        drv.join(5.0)
    assert drv.rec.verdict[a] is True and drv.rec.verdict[b] is True
    assert drv.rec.done[b] < drv.rec.done[a] - 0.5


def test_an_answer_altered_on_its_way_to_the_caller_is_caught():
    def wrap(disp):
        submit = disp.submit
        n = [0]

        def flipped(sets, lane="aggregate"):
            resolve = submit(sets, lane=lane)
            n[0] += 1
            return (lambda: not resolve()) if n[0] == 5 else resolve
        disp.submit = flipped

    res = go(CELLS[1], builder(Device(), wrap))
    assert not res["correct"]
    assert res["compared"]["wrong_verdicts"]["value"] == 1


def test_an_answer_that_never_comes_is_caught(monkeypatch):
    monkeypatch.setattr(run, "DRAIN_S", 1.0)

    def wrap(disp):
        submit = disp.submit

        def lost(sets, lane="aggregate"):
            resolve = submit(sets, lane=lane)
            if lane == "attestation":
                return lambda: time.sleep(30) or resolve()
            return resolve
        disp.submit = lost

    res = go(CELLS[1], builder(Device(), wrap))
    assert not res["correct"]
    assert res["compared"]["no_verdict"]["value"] > 0


def test_an_answer_that_comes_after_the_drain_is_caught(monkeypatch):
    """Closing the stack still answers the batches in flight; a verdict
    that comes only then came too late."""
    monkeypatch.setattr(run, "DRAIN_S", 0.5)
    res = go(CELLS[1], builder(Device(delay=2.0)))
    assert not res["correct"]
    assert res["compared"]["no_verdict"]["value"] > 0
    assert res["failed"] >= res["compared"]["no_verdict"]["value"]


def test_control_the_systems_flaky_verdicts_are_caught():
    from lodestar_tpu.testing import faults

    faults.configure("flaky:0.5")
    try:
        res = go(CELLS[0], builder(Device()))
    finally:
        faults.clear(reset_counters=True)
    assert not res["correct"]
    assert res["compared"]["device_mismatch"]["value"] >= 1


def test_check_reads_the_program_and_its_control(monkeypatch):
    import check

    # a tiny run has few batches: flip more of them than the chip's control
    monkeypatch.setattr(check, "CONTROL", "flaky:0.5")
    name = CELLS[1]
    _, _, mix = run.cell(name)
    out = check.readings(name, [5, 6], [7], 3.0,
                         build=builder(Device()), config=TINY, mix=fast(mix),
                         workers=2)
    assert all(r["correct"] for r in out["program"].values())
    assert not any(r["correct"] for r in out["control"].values())
    assert out["program_reading"]["device_mismatch"] == 0
    assert out["control_reading"]["device_mismatch"] >= 1


def test_a_new_cell_is_new_files_only(tmp_path):
    """Open question 1, homestaker_2sub_1m.range_sync: 32-block segments
    as one call each, closed loop; added as files, nothing edited."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (bench / "traffic" / "range_sync.json").write_text(json.dumps({
        "loop": "closed", "slots": 64, "warmup_slots": 32,
        "requests": [{"kind": "block", "lane": "block", "batchable": False,
                      "blocks_per_call": 32, "due_s": [0.0, 0.0]}]}))
    (bench / "workloads" / "homestaker_2sub_1m.range_sync.json").write_text(
        json.dumps({"name": "homestaker_2sub_1m.range_sync",
                    "config": "homestaker_2sub_1m", "traffic": "range_sync",
                    "chips": 1}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "homestaker_2sub_1m.range_sync",
                              "config": "homestaker_2sub_1m",
                              "traffic": "range_sync", "chips": 1,
                              "why": "range sync"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = importlib.util.spec_from_file_location(
        "run_copy", bench / "run.py")
    mod = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(mod)
    name = "homestaker_2sub_1m.range_sync"
    metrics = mod.metrics_for(name, False)
    assert {m["name"] for m in metrics} == {"setup_s", "sets_per_s"}
    cfg = dict(TINY, block_aggregates=4)
    res = mod.run_cell(name, 7, 8.0, False, build=builder(Device()),
                       config=cfg, workers=2)
    assert res["correct"], res["compared"]
    assert res["metrics"]["sets_per_s"]["value"] > 0
