#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json, one seed, one measured window.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout of the repository, on a machine whose JAX
sees a TPU. A cell (`workloads/<cell>.json`) names a deployment
(`configs/<config>.json`) and a traffic mix (`traffic/<traffic>.json`);
metrics are read by `metrics/<name>.py` (or, for a metric split by cell
as `<name>.<split>`, by `metrics/<name>.py`). Everything is found by the
names in those files, so a new cell, mix, deployment or metric is new
files.

Each run builds the node's verifier stack as the node does at startup
(`chain/verifier_stack.build_verifier_stack`, the node's kernel shapes:
lane dispatcher -> supervisor -> device tier, the CPU tier second), makes
its traffic from the seed (signed here, on the host's cores, while the
stack builds), offers one warm-up slot, then measures for `--seconds`.
Requests offered in the window are then followed to their verdicts.

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (with `--trace 1`, also `busy_s` and
`window_s`, and a `breakdown`), and last `compared`: each number the
correctness check compared, with its limit. The same numbers are the last
lines on stderr. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.

JAX's persistent compile cache and the program's AOT executable store are
kept in `.bench_cache/` inside the checkout, so only the first run of a
checkout compiles.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import threading
import time

T_START = time.monotonic()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
DRAIN_S = 60.0  # how long past the close a window's requests are awaited
TRACE_S = 2.0  # the traced part of a --trace 1 window: its last seconds
SAMPLE = 24  # sets the reference checks in each run


def log(msg: str) -> None:
    print(msg, flush=True)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(name: str) -> tuple[dict, dict, dict]:
    """(workload, config, traffic mix) of a cell, by name."""
    w = load(os.path.join(BENCH, "workloads", name + ".json"))
    return (w, load(os.path.join(BENCH, "configs", w["config"] + ".json")),
            load(os.path.join(BENCH, "traffic", w["traffic"] + ".json")))


def metrics_for(name: str, trace: bool, spec: dict | None = None) -> list[dict]:
    """The metrics BENCHMARK.json asks of cell `name`: its end-to-end
    metrics, or with `trace` its per-layer ones."""
    spec = spec or load(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in mine else [])]


def reader(metric: str):
    """The reader module of a metric: metrics/<name>.py, else, for a
    split metric <base>.<split>, metrics/<base>.py."""
    for stem in (metric, metric.rsplit(".", 1)[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {metric}")


def n_slots(mix: dict, seconds: float) -> int:
    """Slots of traffic a run makes: a closed loop cycles `slots`
    distinct ones; an open loop needs the warm-up, the window and one
    more slot offered while the window's requests drain."""
    if mix["loop"] == "closed":
        return mix["slots"]
    return mix["warmup_slots"] + math.ceil(seconds / mix["slot_s"]) + 1


def p95(values: list[float]) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def setup_env() -> None:
    """Before JAX is imported: the compile cache and the AOT store at a
    fixed path in the checkout (the program keeps its AOT store beside
    the cache), exported when a kernel compiles and loaded otherwise; the
    TPU runtime's logs off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["LODESTAR_TPU_AOT_EXPORT"] = "1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


# -- one run --------------------------------------------------------------------


class CpuTierWatch:
    """Which signature sets the supervisor handed to the CPU tier."""

    def __init__(self, cpu):
        self.ids: set[int] = set()
        for name in ("verify_signature_sets",
                     "verify_signature_sets_individual"):
            setattr(cpu, name, self._wrap(getattr(cpu, name)))

    @classmethod
    def on(cls, cpu) -> "CpuTierWatch":
        """The watch on `cpu`, installed once, emptied for this run."""
        watch = getattr(cpu, "_bench_watch", None)
        if watch is None:
            watch = cpu._bench_watch = cls(cpu)
        watch.ids.clear()
        return watch

    def _wrap(self, fn):
        def call(sets):
            sets = list(sets)
            self.ids.update(id(s) for s in sets)
            return fn(sets)
        return call


def _hist(h) -> tuple[float, int]:
    return sum(h._sums.values()), sum(h._totals.values())


def _pipeline_state(pipeline) -> dict:
    return {
        "stages": pipeline.stage_snapshot(),
        "coalesced": _hist(pipeline.lane_coalesced_sets),
        "planner": pipeline.planner_snapshot(),
        "supervisor": pipeline.supervisor_snapshot(),
        "lanes": pipeline.lanes_snapshot(),
    }


def _delta(after: dict, before: dict) -> dict:
    stages = {
        k: v["sum_s"] - before["stages"].get(k, {}).get("sum_s", 0.0)
        for k, v in after["stages"].items()
    }
    planner = {
        k: v - before["planner"]["sets"].get(k, 0)
        for k, v in after["planner"]["sets"].items()
    }
    decisions = {
        k: v - before["planner"]["decisions"].get(k, 0)
        for k, v in after["planner"]["decisions"].items()
    }
    return {
        "stages": stages,
        "coalesced": (after["coalesced"][0] - before["coalesced"][0],
                      after["coalesced"][1] - before["coalesced"][1]),
        "planner_sets": planner,
        "planner_decisions": decisions,
    }


def _trace_tail(prof_dir: str, t_close: float, traced_s: float) -> None:
    """Trace the last `traced_s` seconds of the window: start the
    profiler (its per-operation device events fill its buffers in some
    tens of seconds) and mark the traced span for the reduction. The
    caller stops the profiler once the window has closed."""
    import shutil

    import jax

    shutil.rmtree(prof_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    time.sleep(max(0.0, t_close - traced_s - 0.5 - time.monotonic()))
    jax.profiler.start_trace(prof_dir, profiler_options=opts)
    time.sleep(max(0.0, t_close - traced_s - time.monotonic()))
    with jax.profiler.TraceAnnotation("bench_window"):
        time.sleep(max(0.0, t_close - time.monotonic()))


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             build=None, config: dict | None = None, mix: dict | None = None,
             workers: int | None = None, metrics: list | None = None,
             session: list | None = None) -> dict:
    """One run of cell `name`; returns the result line's object. `build`
    builds the verifier stack from a PipelineMetrics (the node's
    `build_verifier_stack` at its own shapes by default); `config` and
    `mix` replace the deployment and the traffic mix; tests pass them to
    run at a tiny size on the CPU. `session`, a list, keeps the stack
    open for the next run: empty, it is filled with (stack, pipeline)
    and the stack is left open; filled, that stack serves this run."""
    import concurrent.futures as cf
    import multiprocessing as mp

    import gen
    from drive import Driver

    workload, cfg, cell_mix = cell(name)
    cfg = config or cfg
    mix = mix or cell_mix
    metrics = metrics if metrics is not None else metrics_for(name, trace)
    plan = gen.Plan(cfg, mix, seed, n_slots(mix, seconds))
    workers = workers or max(1, min(12, (os.cpu_count() or 2) - 1))
    pool = cf.ProcessPoolExecutor(workers, mp_context=mp.get_context("spawn"))
    t0 = time.monotonic()
    try:
        collect = gen.make(plan, pool.submit)

        import jax

        from lodestar_tpu.bls import api as bls
        from lodestar_tpu.chain.bls_verifier import BlsShedError
        from lodestar_tpu.observability.compile_ledger import ledger
        from lodestar_tpu.observability.stages import PipelineMetrics

        t_build = time.monotonic()
        if session:
            stack, pipeline = session
        else:
            pipeline = PipelineMetrics()
            if build is None:
                from lodestar_tpu.chain.verifier_stack import (
                    build_verifier_stack,
                )

                stack = build_verifier_stack(pipeline)
            else:
                stack = build(pipeline)
            if session is not None:
                session.extend((stack, pipeline))
        build_s = time.monotonic() - t_build
        events = ledger().snapshot()["events"]
        log(f"setup: stack built in {build_s:.3f}s, kernels {stack.compiled}, "
            f"aot preload {stack.aot}")
        for e in events:
            log(f"setup: compile {e['kernel']} {e['seconds']:.3f}s "
                f"cache={e['cache']} [{e['key']}]")
        t_gen = time.monotonic()
        traffic = collect()
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
    from lodestar_tpu.bls.curve import PointG1
    from lodestar_tpu.bls.fields import Fq

    pks: dict = {}
    sets = []
    for pk, (x, y), msg, sig, _ in traffic.sets:
        key = pks.get(pk)
        if key is None:
            key = pks[pk] = bls.PublicKey(PointG1(Fq(x), Fq(y), Fq(1)), pk)
        sets.append(bls.SignatureSet(pubkey=key, message=msg, signature=sig))
    log(f"setup: traffic {len(traffic.sets)} sets in {plan.n_slots} slots, "
        f"{time.monotonic() - t0:.3f}s from start ({time.monotonic() - t_gen:.3f}s "
        f"waited after the stack build, {workers} workers)")

    watch = CpuTierWatch.on(stack.cpu)
    drv = Driver(stack.dispatcher, pipeline, sets, BlsShedError)
    n_events = len(ledger().snapshot()["events"])
    state_b = _pipeline_state(pipeline)
    prof_dir = None
    t_warm = time.monotonic()
    stop = threading.Event()
    gen_thread = None
    lead = mix.get("lead_sets", 0)
    if mix["loop"] == "closed":
        for k in range(mix["warmup_slots"]):
            rows = drv.closed_slot(traffic.slots[k % len(traffic.slots)],
                                   math.inf, lead)
            drv.wait_done(rows, math.inf)
        t_open = time.monotonic()
    else:
        t_first = time.monotonic() + 0.5
        t_open = t_first + mix["warmup_slots"] * mix["slot_s"]
        gen_thread = threading.Thread(
            target=drv.open_slots, name="bench-open-loop",
            args=(traffic.slots, t_first, mix["slot_s"], stop), daemon=True)
        gen_thread.start()
        time.sleep(max(0.0, t_open - time.monotonic() - 1.0))
    while time.monotonic() < t_open:
        time.sleep(0.001)
    setup_s = t_open - T_START
    state0 = _pipeline_state(pipeline)
    t_close = t_open + seconds
    tracer = None
    if trace:
        prof_dir = os.path.join(CACHE, "trace")
        tracer = threading.Thread(target=_trace_tail, name="bench-trace",
                                  args=(prof_dir, t_close, min(TRACE_S, seconds)))
        tracer.start()
    if mix["loop"] == "closed":
        k = mix["warmup_slots"]
        while time.monotonic() < t_close:
            rows = drv.closed_slot(traffic.slots[k % len(traffic.slots)],
                                   t_close, lead)
            drv.wait_done(rows, t_close)
            k += 1
    else:
        time.sleep(max(0.0, t_close - time.monotonic()))
    state1 = _pipeline_state(pipeline)
    if tracer is not None:
        tracer.join()
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        log(f"trace: stopped in {time.monotonic() - t_stop:.3f}s")
    log(f"setup: warm-up {t_open - t_warm:.3f}s; window opened at "
        f"{setup_s:.3f}s")
    state1 = _pipeline_state(pipeline)
    compiles = len(ledger().snapshot()["events"]) - n_events
    rec = drv.rec
    in_window = [i for i in range(len(rec)) if t_open <= rec.due[i] < t_close]
    drv.wait_done(in_window, t_close + DRAIN_S)
    # read now: closing the stack below still answers what is in flight
    unanswered = {i for i in in_window if rec.done[i] is None}
    stop.set()
    if gen_thread is not None:
        gen_thread.join(10.0)
    drain_s = time.monotonic() - t_close
    state2 = _pipeline_state(pipeline)

    devices = jax.devices()
    used = devices[: workload["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    if session is None:
        stack.close()
    drv.close()
    left = drv.join(15.0)
    if left:
        log(f"threads still running: {left}")

    # -- correctness -----------------------------------------------------------
    import random

    import bls12

    done_ok = [i for i in range(len(rec)) if rec.verdict[i] is not None]
    wrong = sum(1 for i in done_ok
                if rec.verdict[i] != traffic.expect(rec.sets[i]))
    no_verdict = len(unanswered)
    rng = random.Random(seed ^ 0x5EED)
    window_sets = [k for i in in_window if rec.verdict[i] is not None
                   for k in rec.sets[i]]
    by_kind = {}
    for i in in_window:
        if rec.verdict[i] is not None:
            by_kind.setdefault(rec.kind[i], []).extend(rec.sets[i])
    # one of each kind, every invalid set, and SAMPLE drawn from the seed
    sample = [rng.choice(v) for v in by_kind.values()]
    sample += [k for k in window_sets if k in traffic.invalid]
    if window_sets:
        sample += rng.sample(window_sets, min(SAMPLE, len(window_sets)))
    t_ref = time.monotonic()
    ref_disagrees = sum(
        1 for k in sample
        if bls12.reference_verdict(*(traffic.sets[k][i] for i in (4, 0, 2, 3)))
        != (k not in traffic.invalid))
    ref_s = time.monotonic() - t_ref
    mismatches = (state2["supervisor"]["verdict_mismatches"]
                  - state_b["supervisor"]["verdict_mismatches"])
    compared = {
        "wrong_verdicts": {"value": wrong, "limit": 0},
        "no_verdict": {"value": no_verdict, "limit": 0},
        "device_mismatch": {"value": mismatches, "limit": 0},
        "ref_disagrees": {"value": ref_disagrees, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    # -- failures ---------------------------------------------------------------
    fb0 = state0["supervisor"]["fallbacks"]
    fb2 = state2["supervisor"]["fallbacks"]
    cpu_served = sum(v - fb0.get(k, 0) for k, v in fb2.items()
                     if k != "negative_audit")
    failed_ids = unanswered | {i for i in in_window if rec.err[i] is not None}
    if cpu_served:
        failed_ids |= {i for i in in_window
                       if any(id(sets[k]) in watch.ids for k in rec.sets[i])}
    sheds = sum(1 for i in in_window if rec.err[i] == "shed")

    # -- metrics ----------------------------------------------------------------
    window = _delta(state1, state0)
    sets_done = sum(rec.n_sets[i] for i in range(len(rec))
                    if rec.verdict[i] is not None and rec.done[i] is not None
                    and t_open <= rec.done[i] <= t_close)
    reduced = None
    if trace:
        import devtrace

        t_tr = time.monotonic()
        reduced = devtrace.reduce_dir(prof_dir)
        log(f"trace: reduced in {time.monotonic() - t_tr:.3f}s: "
            + (reduced.summary() if reduced else "no device plane"))
    ctx = {
        "cell": name, "window_s": seconds, "setup_s": setup_s,
        "rec": rec, "in_window": in_window, "failed": failed_ids,
        "sets_done": sets_done, "window": window, "trace": reduced,
        "t_open": t_open, "t_close": t_close, "drain_s": DRAIN_S,
        "p95": p95,
    }
    out_metrics = {}
    for m in metrics:
        value = reader(m["name"]).read(ctx)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(in_window),
              "failed": len(failed_ids), "metrics": out_metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["compared"] = compared

    log(f"window: {seconds}s, {len(in_window)} requests due, "
        f"{sets_done} sets verdicted in it, {sheds} shed, "
        f"{len(failed_ids)} failed, {compiles} compiles; drain "
        f"{drain_s:.3f}s; reference {len(sample)} sets in {ref_s:.3f}s")
    answered = [rec.done[i] - rec.due[i] for i in in_window
                if i not in failed_ids and rec.done[i] is not None]
    log(f"window: failed share {len(failed_ids) / max(1, len(in_window)):.6f} "
        f"(shed {sheds}); answered {len(answered)}, their p95 "
        + (f"{p95(answered) * 1e3:.3f}ms" if answered else "none"))
    lumps: dict = {}
    for i in range(len(rec)):
        if rec.verdict[i] is not None and t_open <= rec.done[i] <= t_close:
            t = round(rec.done[i] - t_open, 2)
            lumps[t] = lumps.get(t, 0) + rec.n_sets[i]
    log("window: verdicts handed over in it (s after open, sets): "
        + json.dumps(sorted(lumps.items())[:80]))
    log("window: stages " + json.dumps(
        {k: round(v, 6) for k, v in window["stages"].items()}))
    log("window: planner sets " + json.dumps(window["planner_sets"])
        + " decisions " + json.dumps(window["planner_decisions"]))
    log(f"window: coalesced {window['coalesced']}; lanes "
        + json.dumps(state2["lanes"]))
    log("run: supervisor " + json.dumps(
        {k: v for k, v in state2["supervisor"].items() if k != "faults"}))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    workload, _, _ = cell(args.workload)
    setup_env()
    try:
        import jax
        import lodestar_tpu  # noqa: F401
    except ImportError as e:
        print(f"run: the system under test is not importable: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < workload["chips"]:
        print(f"run: needs {workload['chips']} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for k, c in result["compared"].items():
        print(f"compared {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
