"""device_idle_pct: share of the window in which no operation ran on the
device: 100 * (1 - busy / window), from the trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
