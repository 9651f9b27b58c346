"""sets_per_s: signature sets whose verdicts reached their callers inside
the window, over the window's seconds (host clock). Shed and raised
requests have no verdict and do not count."""


def read(ctx):
    return ctx["sets_done"] / ctx["window_s"]
