"""Per-stage device timing for the verifier pipeline.

The production kernels fuse every stage into ONE XLA dispatch, so host
timers can only see the whole; this module times each stage as its own
jitted sub-kernel (the tools/kernel_profile.py methodology as a library)
and records the steady-state numbers into a `PipelineMetrics` stage
histogram — the bench's stage-time breakdown and the operator's answer
to "where does the dispatch time go".

Inputs are deterministic random limb/bit arrays: every kernel is
branchless with fixed-trip control flow, so stage TIMING is
value-independent — no host-side signing/hashing setup cost. (The
verdicts are meaningless; nothing here checks them.)

The sum of stages exceeds the fused kernel's time (XLA overlaps stages);
the RATIOS say where the next optimization dollar goes (BASELINE.md
round-5 stage profile).
"""

from __future__ import annotations

import time

import numpy as np

from .stages import PipelineMetrics, default_pipeline
from .trace import annotation

N_LIMBS = 32
R_BITS = 64


def _rand_inputs(batch: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    limb = lambda *shape: rng.integers(
        0, 1 << 12, size=shape + (N_LIMBS,), dtype=np.int32
    )
    bits = rng.integers(0, 2, size=(batch, R_BITS), dtype=np.int32)
    raw = rng.integers(0, 256, size=(batch, 96), dtype=np.uint8)
    return limb, bits, raw


def profile_stages(
    pipeline: PipelineMetrics | None = None,
    batch: int = 256,
    reps: int = 2,
) -> dict:
    """Time each pipeline stage at `batch` lanes; returns
    {stage: steady-state seconds} and observes each into the stage
    histogram. `batch` must be a multiple of 4 (MSM subset-4 tables)."""
    import jax

    from ..ops import fp, fp2, fp12, msm
    from ..ops.g2_decompress import decompress
    from ..ops.pairing import (
        final_exponentiation,
        final_exponentiation_batch,
        miller_loop_proj_pq,
    )
    from ..ops.points import g1, g2

    if batch % 4 != 0:
        raise ValueError("batch must be a multiple of 4")
    obs = pipeline if pipeline is not None else default_pipeline()
    limb, bits, raw = _rand_inputs(batch)

    def timed(stage: str, fn, *args):
        from .compile_ledger import ledger

        jitted = ledger().wrap(jax.jit(fn), f"stage_{stage}")
        with annotation(f"stage_profile/{stage}/compile"):
            out = jitted(*args)
            jax.block_until_ready(out)
        t0 = time.monotonic()
        with annotation(f"stage_profile/{stage}"):
            for _ in range(reps):
                out = jitted(*args)
            jax.block_until_ready(out)
        dt = (time.monotonic() - t0) / reps
        obs.observe_stage(stage, dt)
        return out, dt

    results: dict[str, float] = {}

    pk_x, pk_y = limb(batch), limb(batch)
    rpk, results["scalar_mul"] = timed(
        "scalar_mul", lambda b, x, y: g1.scalar_mul_bits(b, (x, y)),
        bits, pk_x, pk_y,
    )

    sig_x, sig_y = limb(batch, 2), limb(batch, 2)
    _, results["msm_planes"] = timed(
        "msm_planes",
        lambda x, y, b: msm.masked_plane_sums(g2, (x, y, fp2.one((batch,))), b),
        sig_x, sig_y, bits,
    )

    _, results["g2_decompress"] = timed("g2_decompress", decompress, raw)

    msg_x, msg_y = limb(batch, 2), limb(batch, 2)
    fs, results["miller_loop"] = timed(
        "miller_loop",
        lambda px, py, qx, qy: miller_loop_proj_pq(
            (px, py, fp.one((batch,))), (qx, qy, fp2.one((batch,)))
        ),
        rpk[0], rpk[1], msg_x, msg_y,
    )

    prod, results["product_tree"] = timed("product_tree", fp12.product_tree, fs)

    _, results["final_exp"] = timed(
        "final_exp", lambda f: fp12.is_one(final_exponentiation(f[None]))[0], prod
    )

    # device tag `bls/final_exp_batch`: the N-wide shared-inversion final
    # exp of the per-set verdict path (ONE easy-part inversion chain for
    # the whole batch — the latency-floor win of ISSUE 14)
    _, results["final_exp_batch"] = timed(
        "final_exp_batch",
        lambda f: fp12.is_one(final_exponentiation_batch(f)), fs,
    )

    return {k: round(v, 6) for k, v in results.items()}
