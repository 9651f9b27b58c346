"""verdict_p95_ms: 95th percentile (nearest rank) over every request due
in the window of the time from when it was due to its verdict, host
clock. A failed request (shed, raised, served by the CPU tier outside its
audit, or with no verdict when the run stopped waiting) misses any limit:
it counts as the longest wait the run allows, from its due time to the
end of the drain."""


def read(ctx):
    rec, end = ctx["rec"], ctx["t_close"] + ctx["drain_s"]
    lat = []
    for i in ctx["in_window"]:
        if i in ctx["failed"] or rec.done[i] is None:
            lat.append(end - rec.due[i])
        else:
            lat.append(rec.done[i] - rec.due[i])
    return ctx["p95"](lat) * 1e3 if lat else None
