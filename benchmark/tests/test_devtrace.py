"""The trace reduction on traces with known answers."""

import pytest

import devtrace

MS = 1_000_000  # ns


def planes(ops, modules, host=()):
    return [
        ("/host:CPU", [("python3", [("bench_window", 0, 100 * MS), *host])]),
        ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules)]),
    ]


def test_busy_union_kernel_time_and_gaps():
    ops = [("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 20 * MS, 20 * MS),
           ("fusion.1", 70 * MS, 40 * MS)]  # overlaps; runs past the window
    modules = [("jit_grouped_verify_kernel_raw(123)", 10 * MS, 30 * MS),
               ("jit_batch_verify_kernel_raw(7)", 70 * MS, 40 * MS)]
    host = [("bls_verify_batch/512", 45 * MS, 20 * MS)]
    r = devtrace.reduce_planes(planes(ops, modules, host))
    assert r.window_s == 0.1
    assert abs(r.busy_s - 0.06) < 1e-12  # [10, 40] + [70, 100]
    assert {k: round(v, 9) for k, v in r.kernels.items()} == {
        "jit_grouped_verify_kernel_raw": 0.03,
        "jit_batch_verify_kernel_raw": 0.03}
    assert [g[0] for g in r.gaps] == ["bls_verify_batch/512", "host idle"]
    assert [round(g[1], 9) for g in r.gaps] == [0.03, 0.01]
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"


def test_nothing_to_read_gives_nothing():
    assert devtrace.reduce_planes([("/host:CPU", [("python3", [])])]) is None
    no_window = [("/device:TPU:0", [("XLA Ops", [("f", 0, 5)])])]
    assert devtrace.reduce_planes(no_window) is None


def test_the_window_ends_where_trace_buffers_were_dropped():
    ops = [("fusion.1", 10 * MS, 80 * MS)]
    modules = [("jit_k(1)", 10 * MS, 80 * MS)]
    p = planes(ops, modules)
    p[1][1].append(("XLA TraceMe", [("Trace Buffers Dropped", 50 * MS,
                                     50 * MS)]))
    r = devtrace.reduce_planes(p)
    assert r.dropped and abs(r.window_s - 0.05) < 1e-12
    assert abs(r.busy_s - 0.04) < 1e-12
    assert abs(r.kernels["jit_k"] - 0.04) < 1e-12


def test_a_recorded_v5e_trace():
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_v5e.json")) as f:
        planes = json.load(f)["planes"]
    r = devtrace.reduce_planes(planes)
    (w0, dur), = [(e[1], e[2]) for _, lines in planes[1:] for _, ev in lines
                  for e in ev if e[0] == "bench_window"]
    w1 = w0 + dur
    ops = [e for name, ev in planes[0][1] if name == "XLA Ops" for e in ev]
    # the busy union by a plain sweep over the operations' edges
    edges = sorted([(max(s, w0), 1) for _, s, d in ops if s < w1]
                   + [(min(s + d, w1), -1) for _, s, d in ops if s < w1])
    busy, depth, since = 0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert r.devices == 1 and not r.dropped
    assert abs(r.window_s - dur * 1e-9) < 1e-12
    assert abs(r.busy_s - busy * 1e-9) < 1e-9
    # one grouped program runs across the whole slice
    assert list(r.kernels) == ["jit_grouped_verify_kernel_raw"]
    assert abs(r.kernels["jit_grouped_verify_kernel_raw"] - r.window_s) < 1e-9
    assert 0 < r.busy_s <= r.window_s
    assert sum(g for _, g in r.gaps) + r.busy_s == pytest.approx(r.window_s)
