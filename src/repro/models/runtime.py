"""Runtime (non-architectural) execution options.

``ArchConfig`` describes the *published* architecture; ``Runtime`` describes how we
execute it (attention implementation, chunk sizes, remat, sharding-oriented knobs).
Keeping them separate lets the perf loop flip execution strategy without touching
the architecture definition — and lets EXPERIMENTS.md record "paper-faithful
baseline" vs "optimized" as two Runtimes over the same ArchConfig.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Runtime:
    # attention
    attn_impl: str = "flash"      # "flash" (chunked online-softmax) | "plain"
    kv_chunk: int = 512           # flash kv-chunk length
    decode_window_only: bool = True  # decode with sliding window when cfg.sliding_window>0 and seq is long
    # memory
    remat: bool = True            # checkpoint each scanned block
    scan_layers: bool = True      # lax.scan over stacked layer params
    # moe
    moe_impl: str = "sort"        # "sort" (expert-parallel dispatch) | "dense" (all-experts; oracle)
    capacity_factor: float = 0.0  # 0 => take from cfg.moe.capacity_factor
    moe_expert_axis: object = None  # mesh axis for the [E,C,D] buffer's E dim
                                    # (forces all-to-all dispatch; §Perf)
    moe_token_axes: object = None   # mesh axes for the flattened token dim
    # ssm
    ssm_chunk: int = 0            # 0 => cfg.ssm.chunk
    # distribution hints (consumed by repro.distributed.sharding)
    seq_parallel: bool = False    # shard activation seq dim on "model" at block boundaries
    act_spec: object = None       # PartitionSpec applied to the scan carry at
                                  # every unit boundary (set by distributed.steps
                                  # when seq_parallel; needs a mesh context)
    act_inner_spec: object = None  # optional second constraint right after the
                                   # boundary one: storage stays seq-sharded but
                                   # compute sees one explicit gather per layer
                                   # (Megatron-SP AG-at-entry), instead of XLA
                                   # re-gathering x for every projection
    # kernels
    use_pallas: bool = False      # route hot ops through Pallas kernels
    pallas_interpret: bool = False  # True only off the TPU (tests)

    def cf(self, cfg) -> float:
        return self.capacity_factor or cfg.moe.capacity_factor

    def sschunk(self, cfg) -> int:
        return self.ssm_chunk or cfg.ssm.chunk


# The paper-era baseline: plain attention, dense-oracle MoE kept only for tests.
BASELINE = Runtime(attn_impl="plain", remat=True, moe_impl="sort")
DEFAULT = Runtime()
