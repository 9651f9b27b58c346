"""Device time per named scope and idle time per program span, on traces
with known answers, and the readers of the metrics built on them."""

import json
import os

import pytest

import run
import xspace

MS = 1_000_000  # ns
DATA = os.path.join(os.path.dirname(__file__), "data")


def planes(ops, host=(), window=(0, 100 * MS), extra=()):
    """One device with `ops` (name, start, dur, scope) and a host thread
    with the window span and `host` spans."""
    return [
        ("/host:CPU", [("python3", [("bench_window", window[0],
                                     window[1] - window[0]), *host]),
                       *extra]),
        ("/device:TPU:0", [("XLA Ops", ops)]),
    ]


def busy_by_sweep(ops, w0, w1):
    """Device busy time by a plain sweep over the edges of the operations
    that last (a zero-length op covers nothing)."""
    ops = [o for o in ops if o[2] > 0 and o[1] < w1]
    edges = sorted([(max(s, w0), 1) for _, s, d, *_ in ops]
                   + [(min(s + d, w1), -1) for _, s, d, *_ in ops])
    busy, depth, since = 0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy * 1e-9


def recorded():
    with open(os.path.join(DATA, "trace_v5e_scopes.json")) as f:
        p = json.load(f)["planes"]
    (w0, dur), = [(e[1], e[2]) for _, lines in p[1:] for _, ev in lines
                  for e in ev if e[0] == "bench_window"]
    ops = [e for name, ev in p[0][1] if name == "XLA Ops" for e in ev]
    return p, ops, w0, w0 + dur


def test_scope_of_takes_the_innermost_bls_scope():
    path = "jit(k)/bls/final_exp/while/body/closed_call/bls/product_tree/mul:"
    assert xspace.scope_of(path) == "bls/product_tree"
    assert xspace.scope_of("jit(k)/bls/miller_loop/dot_general:") == \
        "bls/miller_loop"
    assert xspace.scope_of("jit(k)/reduce_sum:") == xspace.UNSCOPED
    assert xspace.scope_of(None) == xspace.UNSCOPED


def test_self_time_goes_to_the_innermost_op():
    ops = [("%while.1", 10 * MS, 40 * MS, None),  # no tf_op: unscoped
           ("%fusion.1", 12 * MS, 8 * MS, "bls/miller_loop"),
           ("%fusion.2", 25 * MS, 15 * MS, "bls/final_exp_batch"),
           ("%fusion.3", 60 * MS, 10 * MS, "bls/product_tree"),
           ("%copy.1", 65 * MS, 15 * MS, None)]  # overlaps, starts later
    r = xspace.reduce_planes(planes(ops))
    assert {k: round(v, 9) for k, v in r.scopes.items()} == {
        xspace.UNSCOPED: 0.032,  # while: 2 + 5 + 10; copy.1: 15
        "bls/miller_loop": 0.008, "bls/final_exp_batch": 0.015,
        "bls/product_tree": 0.005}
    assert r.busy_s == pytest.approx(0.06, abs=1e-12)


@pytest.mark.parametrize("body", [
    [("%fusion.1", 0, 100 * MS, "bls/msm_planes")],
    [("%fusion.1", 0, 60 * MS, "bls/msm_planes"),
     ("%fusion.2", 60 * MS, 40 * MS, "bls/miller_loop")],
])
def test_a_nested_while_is_not_counted_twice(body):
    ops = [("%while.7", 0, 100 * MS, None), *body]
    r = xspace.reduce_planes(planes(ops))
    assert sum(r.scopes.values()) == pytest.approx(0.1, abs=1e-12)
    assert xspace.UNSCOPED not in r.scopes


def test_the_sweep_partitions_the_window_on_random_nestings():
    import random

    import devtrace

    rng = random.Random(26)
    for _ in range(2000):
        ops = []
        for _ in range(rng.randint(1, 6)):
            s = rng.randint(0, 10)
            ops.append((s, s + rng.randint(1, 6), rng.choice("ab")))
        scopes, idle = xspace.sweep(ops, 0, 20)
        busy = sum(e - s for s, e in devtrace._union(o[:2] for o in ops))
        assert sum(scopes.values()) == busy
        assert sum(e - s for s, e in idle) == 20 - busy


def test_scopes_sum_to_busy_time_on_a_recorded_v5e_trace():
    import devtrace

    p, ops, w0, w1 = recorded()
    r = xspace.reduce_planes(p)
    assert abs(sum(r.scopes.values()) - busy_by_sweep(ops, w0, w1)) < 1e-9
    assert abs(r.busy_s - busy_by_sweep(ops, w0, w1)) < 1e-9
    assert any(k.startswith("bls/") and v > 0 for k, v in r.scopes.items())
    # the same window and busy time as the reduction the benchmark had
    plain = [(n, [(ln, [e[:3] for e in ev]) for ln, ev in lines])
             for n, lines in p]
    d = devtrace.reduce_planes(plain)
    assert d.window_s == r.window_s and abs(d.busy_s - r.busy_s) < 1e-12


HOST = [("lane/batch", 0, 30 * MS), ("stage/marshal", 5 * MS, 10 * MS),
        ("np.asarray", 40 * MS, 5 * MS)]  # not a program span
OTHER_THREAD = ("bls-lane-worker-1", [("supervisor/cpu_tier", 50 * MS,
                                       8 * MS)])


def test_idle_by_span_and_the_unattributed_rest():
    ops = [("%a", 20 * MS, 20 * MS, None), ("%b", 55 * MS, 35 * MS, None)]
    r = xspace.reduce_planes(planes(ops, HOST, extra=[OTHER_THREAD]))
    # idle: [0, 20], [40, 55], [90, 100]
    assert {k: round(v, 9) for k, v in r.idle_by_span.items()} == {
        "lane/batch": 0.02, "stage/marshal": 0.01,
        "supervisor/cpu_tier": 0.005}
    assert r.idle_unattributed_s == pytest.approx(0.02, abs=1e-12)
    assert r.idle_under(["stage/marshal", "supervisor/cpu_tier"]) == \
        pytest.approx(0.015, abs=1e-12)


@pytest.mark.parametrize("source", ["synthetic", "recorded"])
def test_idle_splits_into_span_covered_and_unattributed(source):
    if source == "recorded":
        r = xspace.reduce_planes(recorded()[0])
    else:
        ops = [("%a", 20 * MS, 20 * MS, None), ("%b", 55 * MS, 35 * MS, None)]
        r = xspace.reduce_planes(planes(ops, HOST, extra=[OTHER_THREAD]))
    idle = r.window_s - r.busy_s
    covered = r.idle_under(list(r.spans))
    assert covered + r.idle_unattributed_s == pytest.approx(idle, abs=1e-9)
    assert all(v <= covered + 1e-12 for v in r.idle_by_span.values())


def test_the_window_ends_where_trace_buffers_were_dropped():
    ops = [("%a", 10 * MS, 80 * MS, "bls/scalar_mul")]
    p = planes(ops)
    p[1][1].append(("XLA TraceMe", [("Trace Buffers Dropped", 50 * MS,
                                     50 * MS)]))
    r = xspace.reduce_planes(p)
    assert r.window_s == pytest.approx(0.05)
    assert r.scopes == {"bls/scalar_mul": pytest.approx(0.04)}


def test_nothing_to_read_gives_nothing():
    assert xspace.reduce_planes([("/host:CPU", [("python3", [])])]) is None
    no_window = [("/device:TPU:0", [("XLA Ops", [("f", 0, 5, None)])])]
    assert xspace.reduce_planes(no_window) is None


def test_load_reads_times_names_and_scopes_of_an_xplane(tmp_path):
    space = xspace._schema()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=7).value.name = "tf_op"
    for key, name, tf_op in ((1, "%fusion.3 = f32[8] fusion(...)",
                              "jit(k)/bls/miller_loop/mul:"),
                             (2, "%while.1 = (s32[]) while(...)", None)):
        entry = dev.event_metadata.add(key=key)
        entry.value.name = name
        if tf_op:
            entry.value.stats.add(metadata_id=7, str_value=tf_op)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    ops.events.add(metadata_id=2, offset_ps=1_000_500, duration_ps=9_999)
    ops.events.add(metadata_id=1, offset_ps=2_000_000, duration_ps=3_000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "stage/marshal"
    line = host.lines.add(name="python3", timestamp_ns=100)
    line.events.add(metadata_id=1, offset_ps=2_000, duration_ps=5_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    (dname, dlines), (hname, hlines) = xspace.load(str(path))
    assert dname == "/device:TPU:0" and hname == "/host:CPU"
    # whole ns, as jax.profiler.ProfileData gives them
    assert dlines == [("XLA Ops", [
        ("%while.1 = (s32[]) while(...)", 1000, 9, xspace.UNSCOPED),
        ("%fusion.3 = f32[8] fusion(...)", 2000, 3, "bls/miller_loop")])]
    assert hlines == [("python3", [("stage/marshal", 102, 5, None)])]


# -- the readers ----------------------------------------------------------------


def scoped(**kw):
    base = dict(window_s=2.0, busy_s=1.5,
                scopes={"bls/miller_loop": 0.5, "bls/final_exp": 0.1,
                        "bls/final_exp_batch": 0.2, xspace.UNSCOPED: 0.7},
                idle=[(0, 2 * MS), (10 * MS, 12 * MS)],
                spans={"stage/marshal": [[0, 1 * MS]],
                       "stage/device_wait": [[11 * MS, 20 * MS]],
                       "lane/batch": [[0, 20 * MS]]})
    base.update(kw)
    return xspace.Scoped(**base)


def ctx(**kw):
    c = {"trace": object(), "sets_done": 4000, "window_s": 48.0,
         "window": {"stages": {"marshal": 1.0, "cpu_tier": 2.0}}}
    c["xspace"] = scoped()
    c.update(kw)
    return c


def test_scope_readers_scale_the_traced_span_to_the_window():
    # 0.5 s of a 2 s traced span, 24 times over in a 48 s window, 4 ksets
    assert run.reader("miller_loop_ms_per_kset.rate").read(ctx()) == \
        pytest.approx(0.5 * 24 * 1e3 / 4)
    assert run.reader("final_exp_ms_per_kset.rate").read(ctx()) == \
        pytest.approx(0.3 * 24 * 1e3 / 4)


def test_host_stage_idle_and_cpu_tier_readers():
    # idle under the host stages' spans: [0, 1] ms of a 2 s span; the idle
    # [11, 12] ms under stage/device_wait is the device's, not the host's
    assert run.reader("host_stage_idle_pct.rate").read(ctx()) == \
        pytest.approx(100 * 0.001 / 2.0)
    assert run.reader("cpu_tier_ms_per_kset.rate").read(ctx()) == \
        pytest.approx(2.0 * 1e3 / 4)
    idle_tier = ctx(window={"stages": {"marshal": 1.0, "cpu_tier": 0.0}})
    assert run.reader("cpu_tier_ms_per_kset.p95").read(idle_tier) == 0.0


@pytest.mark.parametrize("span", ["stage/device_wait", "stage/bisect"])
def test_idle_under_the_device_wait_or_the_bisection_alone_reads_0(span):
    only = ctx(xspace=scoped(spans={span: [[0, 20 * MS]],
                                    "lane/batch": [[0, 20 * MS]]}))
    assert run.reader("host_stage_idle_pct.rate").read(only) == 0.0


@pytest.mark.parametrize("metric", [
    "miller_loop_ms_per_kset.rate", "final_exp_ms_per_kset.rate",
    "host_stage_idle_pct.rate", "cpu_tier_ms_per_kset.p95"])
def test_readers_give_nothing_without_a_trace_or_sets(metric):
    if not metric.startswith("host_stage"):
        assert run.reader(metric).read(ctx(sets_done=0)) is None
    if not metric.startswith("cpu_tier"):
        assert run.reader(metric).read(ctx(trace=None)) is None


def test_readers_give_nothing_for_a_program_without_the_spans():
    # what a program without the spans leaves: no stage/ span on the
    # trace, no cpu_tier stage in the window
    no_spans = ctx(xspace=scoped(spans={"lane/batch": [[0, 20 * MS]]}))
    assert run.reader("host_stage_idle_pct.rate").read(no_spans) is None
    no_tier = ctx(window={"stages": {"marshal": 1.0}})
    assert run.reader("cpu_tier_ms_per_kset.rate").read(no_tier) is None
