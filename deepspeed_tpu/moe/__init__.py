"""Mixture-of-experts / expert parallelism (reference ``deepspeed/moe/``)."""

from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import (Experts, MOELayer, SortedRouting, TopKGate,
                                           top1gating, top1routing, top2gating, top2routing,
                                           topkgating, topkrouting)
from deepspeed_tpu.moe.mappings import drop_tokens, gather_tokens
from deepspeed_tpu.moe.utils import (has_moe_layers, is_moe_param, split_params_into_different_moe_groups_for_optimizer)

__all__ = [
    "MoE", "MOELayer", "TopKGate", "Experts", "SortedRouting",
    "top1gating", "top2gating", "top1routing", "top2routing", "topkgating", "topkrouting",
    "drop_tokens", "gather_tokens",
    "has_moe_layers", "is_moe_param", "split_params_into_different_moe_groups_for_optimizer"
]
