"""host_stage_idle_pct: 100 * the device-0 idle time inside the union of
the device tier's host-stage spans over the traced span: `stage/<s>` for
each stage `host_stage_ms_per_kset` sums (marshal, hash_to_curve, rand,
dispatch), `lane/h2c_dedup` and `supervisor/cpu_tier`. The host blocked
on the device (`stage/device_wait`) and the bisection (`stage/bisect`,
itself a run of device calls) are left out: idle under them is the
device's or the transfer's, not the host's. None where the trace holds no
`stage/` span."""

import run
import xspace

OTHERS = ("lane/h2c_dedup", "supervisor/cpu_tier")


def read(ctx):
    tr = xspace.of_run(ctx)
    if tr is None or tr.window_s <= 0 or not any(
            n.startswith("stage/") for n in tr.spans):
        return None
    stages = run.reader("host_stage_ms_per_kset").STAGES
    names = [f"stage/{s}" for s in stages] + list(OTHERS)
    return 100.0 * tr.idle_under(names) / tr.window_s
