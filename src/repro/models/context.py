"""RunContext: everything the model needs to know about the runtime substrate."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax


@dataclasses.dataclass(frozen=True)
class RunContext:
    """Static execution context threaded through model apply functions.

    mesh=None means single-device execution (smoke tests, local venue) — all
    distributed code paths (shard_map MoE, FSDP gathers) degrade to local math.
    """

    mesh: Optional[jax.sharding.Mesh] = None
    dp_axes: Tuple[str, ...] = ("data",)       # ("pod","data") when multi-pod
    model_axis: str = "model"
    # xla | pallas (Mosaic kernels, TPU) | interpret (the same kernels in
    # the Pallas interpreter: kernel tests on the CPU)
    impl: str = "xla"
    remat: str = "full"                        # none | dots | full
    # paged decode kernel: GQA-fused flash-decoding grid (B, Hkv, M) vs the
    # per-query-head grid (B, Hq, M) — kept only as the A/B baseline
    paged_fused: bool = True
    moe_capacity_factor: float = 1.25
    # hillclimb knobs (see EXPERIMENTS.md §Perf)
    seq_shard_attn: bool = False               # sequence-parallel attention
    loss_chunk: int = 0                        # 0 = unchunked cross-entropy
    # analysis: fully unroll the layer scan so cost_analysis sees every layer
    scan_unroll: bool = False
    # gradient accumulation: split the global batch into k microbatches
    microbatches: int = 1
    # "tp" (default: Megatron TP + FSDP) | "zero-sp" (weights FSDP-only,
    # sequence sharded over the model axis; dense archs, prefill/decode)
    sharding_profile: str = "tp"

    @property
    def kernels(self) -> bool:
        """Whether attention and scans run the Pallas kernels."""
        return self.impl != "xla"

    @property
    def interpret(self) -> bool:
        return self.impl == "interpret"

    @property
    def zero_sp(self) -> bool:
        return self.sharding_profile == "zero-sp"

    @property
    def fsdp_weights(self) -> bool:
        # serving lowers with weights resident (no optimizer state): no
        # per-layer FSDP gathers on the decode path
        return self.sharding_profile != "serve"

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    def dp_spec(self):
        """PartitionSpec entry for the batch dim."""
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]
