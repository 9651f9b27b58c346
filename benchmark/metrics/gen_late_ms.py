"""gen_late_ms: 95th percentile of how late the traffic generator offered
the window's requests against their schedule (host clock), so that a
starved generator is not read as a fast node."""


def read(ctx):
    rec = ctx["rec"]
    late = [rec.offered[i] - rec.due[i] for i in ctx["in_window"]]
    return ctx["p95"](late) * 1e3 if late else None
