"""cpu_tier_ms_per_kset: seconds the supervisor spent on the CPU tier in
the window (its `cpu_tier` stage: every fallback reason, the negative
audit of a batch the device rejected among them) in ms per 1,000
signature sets verdicted in the window. None where the run observed no
such stage."""


def read(ctx):
    stages = ctx["window"]["stages"]
    if "cpu_tier" not in stages or not ctx["sets_done"]:
        return None
    return stages["cpu_tier"] * 1e3 / (ctx["sets_done"] / 1e3)
