"""host_stage_ms_per_kset: the device tier's host stages in the window
(its own marshal, hash_to_curve, rand and dispatch timers) in ms per
1,000 signature sets verdicted in the window."""

STAGES = ("marshal", "hash_to_curve", "rand", "dispatch")


def read(ctx):
    if not ctx["sets_done"]:
        return None
    s = sum(ctx["window"]["stages"].get(k, 0.0) for k in STAGES)
    return s * 1e3 / (ctx["sets_done"] / 1e3)
