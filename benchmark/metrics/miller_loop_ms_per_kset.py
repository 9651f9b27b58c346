"""miller_loop_ms_per_kset: device-0 self time of the ops traced under
`jax.named_scope("bls/miller_loop")`, scaled to the window as
`kernel_ms_per_kset` does, in ms per 1,000 signature sets verdicted in
the window (xspace.py)."""

import xspace


def read(ctx):
    return xspace.scope_ms_per_kset(ctx, ("bls/miller_loop",))
