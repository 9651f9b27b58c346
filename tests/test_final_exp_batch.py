"""Differential suite for the shared-inversion batched final
exponentiation (`pairing.final_exponentiation_batch`, ISSUE 14).

Every verdict path — per-set, grouped, pk-grouped, split, bisect root,
bisect probe, and the sharded twins — now routes its final exps through
this one entry, so it must be bit-identical to per-lane
`final_exponentiation` on random inputs AND on the edges the Montgomery
product trick is worst at: the identity lane and the non-invertible
all-zero lane (a single zero would otherwise poison the whole batch's
shared inversion; the kernel substitutes the identity and forces that
lane's inverse back to zero, reproducing per-lane `inv(0) = 0^(p-2) = 0`
exactly).

The routing assertion is fast tier (pure source scan); the numeric
differential compiles two deep final-exp kernels (~1-2 min each on CPU)
and lives in the slow tier with the rest of the deep-kernel compiles.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lodestar_tpu.ops import fp, fp12
from lodestar_tpu.ops import pairing as dp

RNG = np.random.default_rng(909)


def test_all_verdict_paths_route_batched_fe():
    """No verdict path may call per-lane `final_exponentiation` directly:
    the only surviving call site is the bench comparison baseline
    (`individual_verify_kernel_legacy_fe`)."""
    import inspect

    from lodestar_tpu.parallel import sharded, verifier

    bare_call = re.compile(r"\bfinal_exponentiation\(")
    v_calls = bare_call.findall(inspect.getsource(verifier))
    s_calls = bare_call.findall(inspect.getsource(sharded))
    assert len(v_calls) == 1, (
        "verifier.py may call per-lane final_exponentiation exactly once "
        "(the legacy-FE bench baseline); found %d call sites" % len(v_calls)
    )
    assert not s_calls, "sharded.py must route final_exponentiation_one/_batch"
    legacy_src = inspect.getsource(verifier.individual_verify_kernel_legacy_fe)
    assert bare_call.search(legacy_src), (
        "the one bare call site must be the legacy-FE bench baseline"
    )


@pytest.mark.slow
def test_batched_matches_per_lane_on_random_and_edge_lanes():
    # random field elements: 12-bit limbs with the top limb cut to 8
    # bits keep every coordinate below 2^380 < p, inside the multiply's
    # [0, 2p) input contract (wider limbs leave it, and then two orders
    # of the same products need not agree even after canonicalizing)
    def element():
        limbs = RNG.integers(0, 1 << 12, size=(2, 3, 2, 32), dtype=np.int32)
        limbs[..., -1] &= (1 << 8) - 1
        return jnp.asarray(limbs)

    lanes = [element() for _ in range(2)]
    lanes.append(fp12.one(()))   # identity lane
    lanes.append(fp12.zero(()))  # non-invertible lane (fallback path)
    fs = jnp.stack(lanes)

    per = jax.jit(dp.final_exponentiation)(fs)
    bat = jax.jit(dp.final_exponentiation_batch)(fs)
    # bit-identical AFTER canonicalization: the two tails may differ in
    # which Montgomery representative they leave, but the verdict
    # comparisons (`fp12.is_one`/`eq`) canonicalize — and in practice the
    # smoke runs came out raw-identical too
    assert bool(jnp.all(fp.canonical(bat) == fp.canonical(per)))
    # the zero lane must map to zero (per-lane Fermat: 0^(p-2) = 0),
    # never poison its neighbors
    assert bool(jnp.all(fp.canonical(bat[-1]) == 0))
    assert bool(jnp.all(fp.canonical(bat[:-1]) == fp.canonical(per[:-1])))
    # the n = 1 wrapper every single-product verdict path uses
    one = jax.jit(dp.final_exponentiation_one)(fs[0])
    assert bool(jnp.all(fp.canonical(one) == fp.canonical(per[0])))
