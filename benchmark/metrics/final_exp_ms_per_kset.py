"""final_exp_ms_per_kset: device-0 self time of the ops traced under
`bls/final_exp` and `bls/final_exp_batch`, scaled to the window as
`kernel_ms_per_kset` does, in ms per 1,000 signature sets verdicted in
the window (xspace.py)."""

import xspace


def read(ctx):
    return xspace.scope_ms_per_kset(ctx, ("bls/final_exp",
                                          "bls/final_exp_batch"))
