"""lane_batch_sets: mean signature sets per lane-dispatcher batch in the
window, from `lodestar_bls_lane_coalesced_sets` (sum over count)."""


def read(ctx):
    total, batches = ctx["window"]["coalesced"]
    return total / batches if batches else None
