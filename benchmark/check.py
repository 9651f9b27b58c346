#!/usr/bin/env python3
"""Correctness readings of one cell over many seeds, and of its control.

    python3 benchmark/check.py --workload <cell> --seeds 101,102,103 \\
        --control-seeds 201,202,203 --seconds 12

The benchmark's runs do not run this. It builds the node's verifier stack
once and makes one run of the cell per seed through it (each with its
own traffic, warm-up, window and drain, as `run.py` makes them). Then,
for each control seed, it builds a fresh stack (so that no breaker state
carries over), arms the system's own fault path
(`lodestar_tpu.testing.faults`, `CONTROL`: a fifth of the device's
verdicts of valid batches reported invalid) and runs the seed. Each run prints its compared numbers; the last line of
stdout is a JSON summary. The lower reading of each number is the
largest over the program's seeds, the upper the smallest over the
control's; PERF.md sets each limit from them. Needs a TPU, as run.py.
"""

from __future__ import annotations

import argparse
import json
import sys

import run

CONTROL = "flaky:0.2"


def readings(workload: str, seeds, control_seeds, seconds: float,
             **sizes) -> dict:
    """Each seed's compared numbers; `sizes` (build, config, mix) go to
    `run.run_cell`, for a run at a tiny size on the CPU."""
    from lodestar_tpu.testing import faults

    out = {"program": {}, "control": {}}
    todo = [("program", s) for s in seeds] + [("control", s)
                                                for s in control_seeds]
    session: list = []
    try:
        for side, seed in todo:
            if side == "control":
                if session:
                    session[0].close()
                    session.clear()
                faults.configure(CONTROL)
            res = run.run_cell(workload, seed, seconds, False,
                               session=session, metrics=[], **sizes)
            got = {k: c["value"] for k, c in res["compared"].items()}
            out[side][seed] = dict(got, correct=res["correct"],
                                   attempted=res["attempted"],
                                   failed=res["failed"])
            run.log(f"check {side} seed {seed}: {json.dumps(out[side][seed])}")
            faults.clear(reset_counters=True)
    finally:
        faults.clear(reset_counters=True)
        if session:
            session[0].close()
    for side, agg in (("program", max), ("control", min)):
        rows = list(out[side].values())
        if rows:
            out[side + "_reading"] = {
                k: agg(r[k] for r in rows) for k in rows[0]
                if k not in ("correct", "attempted", "failed")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, run.ROOT)
    run.setup_env()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("check: no TPU", file=sys.stderr)
        return 2
    out = readings(args.workload,
                   [int(s) for s in args.seeds.split(",") if s],
                   [int(s) for s in args.control_seeds.split(",") if s],
                   args.seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
