"""kernel_ms_per_kset: device time of the jitted programs (the trace's XLA
Modules line, by program name) per 1,000 signature sets: the programs'
share of the traced part of the window, times the window, over the sets
verdicted in the window. Every program the device runs in a run is the
verifier's."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.kernels or not ctx["sets_done"] or tr.window_s <= 0:
        return None
    kernel_s = sum(tr.kernels.values()) / tr.window_s * ctx["window_s"]
    return kernel_s * 1e3 / (ctx["sets_done"] / 1e3)
