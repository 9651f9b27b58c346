"""The node's device signature-verification stack, built in one place.

`BlsLaneDispatcher → SupervisedBlsVerifier → DeviceBlsVerifier`, with
`CpuBlsVerifier` as the supervisor's second tier:

- the lane dispatcher coalesces concurrent verify requests into device
  batches, drains them in lane priority order (block > sync committee >
  aggregate > attestation) and sheds attestations first under flood
  (chain/dispatcher.py; its limits are the LODESTAR_TPU_LANE_* knobs);
- the supervisor owns the failure policy — per-dispatch deadlines, one
  retry, the CPU tier as fallback, the negative-verdict audit and the
  circuit breaker (chain/supervisor.py, docs/robustness.md);
- the device tier runs the XLA kernels (parallel/verifier.py), sharded
  over the chips of the host when there are several (parallel/mesh.py).

`node/node.py` builds this for `--tpu-verifier`, and `chip_smoke.py`
builds the same stack to drive it on the chip.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = [
    "BUCKETS",
    "GROUPED_CONFIGS",
    "VerifierStack",
    "build_verifier_stack",
]

# The device tier's kernel shapes: one set for every node, all compiled
# before the stack serves. Sized for a node on all 64 attestation
# subnets at 1M validators: a slot brings 1M / 32 slots / 64 committees
# ≈ 488 unaggregated attestations per subnet, one signing root each —
# 64 roots × 512 lanes = 32,768 sets, one grouped dispatch (the
# LODESTAR_TPU_LANE_* defaults coalesce and queue that much). One
# per-set bucket of 1,024 lanes takes a block's ~131 sets and keeps the
# per-set and bisection paths at one compiled shape each; a failed
# 32,768-set batch then takes 32 bisection dispatches to pinpoint its
# bad sets, not 256 at 128 lanes. The pk-grouped kernel keeps its
# default 128 pubkeys × 32 messages. Each kernel costs minutes of TPU
# compile, so every shape added here lengthens a cold start.
BUCKETS = (1024,)
GROUPED_CONFIGS = ((64, 512),)


@dataclass
class VerifierStack:
    dispatcher: object  # BlsLaneDispatcher — what the chain verifies through
    supervisor: object  # SupervisedBlsVerifier
    device: object  # DeviceBlsVerifier
    cpu: object  # CpuBlsVerifier
    aot: dict  # compile ledger preload summary
    compiled: list  # kernel names compiled (or loaded) ahead

    def close(self) -> None:
        self.dispatcher.close()
        self.supervisor.close()


def build_verifier_stack(pipeline, prom=None, buckets=BUCKETS,
                         grouped_configs=GROUPED_CONFIGS) -> VerifierStack:
    """Build the stack on `pipeline` (the node's PipelineMetrics) and
    `prom` (its beacon metrics bundle, for the dispatcher's queue
    families), ready to serve: the persistent compile cache is on before
    any trace, every persisted AOT executable for this build is loaded,
    and every kernel the device tier can dispatch is compiled — a first
    dispatch that compiled would outlast the supervisor's deadline and
    be served by the CPU tier. `buckets` and `grouped_configs` are the
    kernel shapes; only tests shrink them."""
    from ..observability.compile_ledger import ledger
    from ..utils.jax_env import enable_compile_cache
    from .bls_verifier import CpuBlsVerifier, DeviceBlsVerifier
    from .dispatcher import BlsLaneDispatcher
    from .supervisor import SupervisedBlsVerifier

    enable_compile_cache()
    device = DeviceBlsVerifier(
        buckets=buckets, grouped_configs=grouped_configs, observer=pipeline
    )
    aot = ledger().preload_aot()
    compiled = device.precompile()
    cpu = CpuBlsVerifier()
    supervisor = SupervisedBlsVerifier(device, cpu, observer=pipeline)
    dispatcher = BlsLaneDispatcher(supervisor, prom=prom)
    return VerifierStack(dispatcher, supervisor, device, cpu, aot, compiled)
