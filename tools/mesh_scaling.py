"""Virtual-mesh scaling table for the sharded grouped verifier.

Runs the SAME total batch (64 root-rows × 64 lanes = 4096 sets) on
1/2/4/8-device virtual CPU meshes and records steady-state sets/s plus
verdict parity with the single-device kernel (VERDICT r2 next-step #7).
CPU-mesh numbers measure the SHARDING (collective layout, per-chip graph),
not TPU silicon — the table's point is that the ICI tier composes and
scales, with real-chip numbers to follow on multi-chip hardware.

Round-7 instrumentation: the 2-device row has sat anomalously BELOW the
4/8-device rows since round 4 (84 vs ~106 sets/s). To attribute it, each
sharded size is now timed twice — the full kernel AND a local-only probe
(`make_sharded_grouped_local_probe`: the per-chip body + u-plane
all_gather, root tail replaced by a psum checksum) — and per-rep times
are recorded so a one-off scheduler hiccup can't masquerade as a
structural cost. body_s vs full_s splits the anomaly into "data-parallel
body" vs "sequential tail + cross-chip product".
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from lodestar_tpu.utils.jax_env import force_platform

N_MAX = int(os.environ.get("MESH_MAX", "8"))
force_platform("cpu", N_MAX)

import jax

from lodestar_tpu.utils.jax_env import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np
from jax.sharding import Mesh

REPS = int(os.environ.get("MESH_REPS", "3"))
# local-only probe sizes: the anomalous size + its healthy comparator
# (instrumenting 8 as well doubles nothing diagnostic and costs another
# deep compile on the 1-core box)
PROBE_SIZES = tuple(
    int(s) for s in os.environ.get("MESH_PROBE_SIZES", "2,4").split(",") if s
)


def _time_reps(fn) -> list[float]:
    times = []
    for _ in range(REPS):
        t0 = time.monotonic()
        out = fn()
        jax.block_until_ready(out)
        times.append(round(time.monotonic() - t0, 3))
    return times


def main():
    from __graft_entry__ import _example_grouped
    from lodestar_tpu.parallel.sharded import (
        ShardedGroupedVerifier,
        make_sharded_grouped_local_probe,
    )
    from lodestar_tpu.parallel.verifier import BatchVerifier

    rows, lanes = 64, 64
    g, a_bits, b_bits = _example_grouped(rows, lanes)
    table = []

    # single-device reference verdict (the unsharded kernel)
    bv = BatchVerifier(grouped_configs=((rows, lanes),))
    t0 = time.monotonic()
    ref = bool(bv.verify_grouped(g, a_bits, b_bits))
    compile_1 = time.monotonic() - t0
    times = _time_reps(lambda: bv.verify_grouped(g, a_bits, b_bits))
    dt = sum(times) / len(times)
    table.append(
        {"devices": 1, "sets_per_sec": round(rows * lanes / dt, 1),
         "verdict": ref, "compile_s": round(compile_1, 1),
         "rep_s": times}
    )
    print(table[-1], flush=True)
    assert ref, "reference verdict False on a valid batch"

    sizes = [n for n in (2, 4, 8) if n <= N_MAX]
    for n in sizes:
        mesh = Mesh(np.array(jax.devices()[:n]), axis_names=("dp",))
        v = ShardedGroupedVerifier(mesh)
        t0 = time.monotonic()
        ok = v.verify_grouped(g, a_bits, b_bits)
        compile_s = time.monotonic() - t0
        assert ok == ref, f"verdict parity broken at {n} devices"
        times = _time_reps(lambda: v.verify_grouped(g, a_bits, b_bits))
        dt = sum(times) / len(times)
        row = {"devices": n, "sets_per_sec": round(rows * lanes / dt, 1),
               "verdict": bool(ok), "compile_s": round(compile_s, 1),
               "rep_s": times,
               "per_chip_miller_lanes": 2 * (rows // n) + 64 // n}
        if n in PROBE_SIZES:
            probe = make_sharded_grouped_local_probe(mesh)
            sharding = v._sharding
            put = lambda x: jax.device_put(x, sharding)
            args = (put(g.pk_x), put(g.pk_y), put(g.msg_x), put(g.msg_y),
                    put(g.sig_x), put(g.sig_y), put(a_bits), put(b_bits),
                    put(g.valid))
            t0 = time.monotonic()
            jax.block_until_ready(probe(*args))
            row["body_compile_s"] = round(time.monotonic() - t0, 1)
            body_times = _time_reps(lambda: probe(*args))
            row["body_rep_s"] = body_times
            body_dt = sum(body_times) / len(body_times)
            row["body_s"] = round(body_dt, 3)
            row["tail_s"] = round(dt - body_dt, 3)
        table.append(row)
        print(table[-1], flush=True)

    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "MESH_SCALING.json"
    )
    note = (
        "All virtual devices share ONE physical core, so total throughput "
        "cannot rise with mesh size — this table measures SHARDING OVERHEAD "
        "(distance from the 1-device unsharded kernel), not silicon scaling. "
        "Round-4 fix still in force: the sequential tail runs on chip 0 only. "
        "Round-7 instrumentation: body_s times the data-parallel local body "
        "(+ u-plane all_gather) with the root tail replaced by a psum "
        "checksum; tail_s = full − body attributes the remainder to the "
        "cross-chip Fp12 product + final exp. rep_s lists raw per-rep "
        "times (reps=%d) so run-to-run noise is visible. See BASELINE.md "
        "§mesh for the 2-device-row analysis." % REPS
    )
    with open(out_path, "w") as f:
        json.dump({"shape": f"{rows}x{lanes}", "platform": "cpu-virtual",
                   "note": note, "reps": REPS, "table": table}, f, indent=2)
    print(json.dumps(table))


if __name__ == "__main__":
    main()
