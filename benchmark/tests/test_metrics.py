"""BENCHMARK.json, the metric readers and their arithmetic."""

import json
import os
import re

import run
from conftest import ROOT


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_reports_what_the_contract_asks():
    s = spec()
    for w in s["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(w["name"], False, s)}
        per_layer = run.metrics_for(w["name"], True, s)
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert per_layer, w["name"]
        assert all(m["moves"] in e2e for m in per_layer)
        run.cell(w["name"])  # its files exist


def test_every_metric_has_a_reader_and_names_are_clean():
    s = spec()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for m in s["end_to_end"] + s["per_layer"]:
        assert name.match(m["name"])
        assert callable(run.reader(m["name"]).read)
    layers = {}
    for m in s["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_p95_is_nearest_rank():
    assert run.p95(list(range(1, 101))) == 95
    assert run.p95([5.0]) == 5.0
    assert run.p95(list(range(1, 21))) == 19


class Rec:
    def __init__(self, due, offered, done):
        self.due, self.offered, self.done = due, offered, done


def test_latency_counts_a_failed_request_as_the_longest_wait():
    rec = Rec([0.0] * 20, [0.001] * 20, [1.0] * 19 + [None])
    ctx = {"rec": rec, "in_window": list(range(20)), "failed": {19},
           "t_close": 10.0, "drain_s": 60.0, "p95": run.p95}
    assert run.reader("verdict_p95_ms").read(ctx) == 1000.0
    ctx["failed"] = {18, 19}
    assert run.reader("verdict_p95_ms").read(ctx) == 70_000.0
    assert abs(run.reader("gen_late_ms.p95").read(ctx) - 1.0) < 1e-9


def test_rate_and_per_kset_readers():
    class Trace:
        kernels = {"jit_a": 2.0, "jit_b": 1.0}
        busy_s, window_s = 9.0, 10.0

    ctx = {"sets_done": 3000, "window_s": 10.0, "setup_s": 7.0,
           "trace": Trace(),
           "window": {"coalesced": (6000.0, 3),
                      "stages": {"marshal": 0.5, "hash_to_curve": 0.25,
                                 "rand": 0.125, "dispatch": 0.125,
                                 "device_wait": 9.0}}}
    assert run.reader("sets_per_s").read(ctx) == 300.0
    assert run.reader("setup_s").read(ctx) == 7.0
    assert run.reader("lane_batch_sets.rate").read(ctx) == 2000.0
    assert run.reader("host_stage_ms_per_kset.rate").read(ctx) == 1000.0 / 3
    assert run.reader("kernel_ms_per_kset.p95").read(ctx) == 1000.0
    Trace.window_s = 5.0  # half the window traced: twice the share
    assert run.reader("kernel_ms_per_kset.p95").read(ctx) == 2000.0
    Trace.window_s = 10.0
    assert abs(run.reader("device_idle_pct.rate").read(ctx) - 10.0) < 1e-9
    ctx["trace"] = None
    assert run.reader("kernel_ms_per_kset.rate").read(ctx) is None
    assert run.reader("device_idle_pct.p95").read(ctx) is None
