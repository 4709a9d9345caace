"""User-facing MoE module (reference ``deepspeed/moe/layer.py:16``).

API parity with ``deepspeed.moe.layer.MoE``: same constructor knobs
(``num_experts``, ``ep_size``, ``k``, capacity factors, ``use_residual``
PR-MoE, noisy gate policy, RTS) and the same return contract
``(output, l_aux, exp_counts)``.

TPU-native notes: the reference's ``_create_process_groups``
(``layer.py:85``) builds expert + expert-data NCCL groups; here expert
placement is the ``expert`` mesh axis (``parallel/topology.py``) and
``ep_size`` is validated against it rather than creating anything.
"""

from typing import Optional, Tuple

import jax.numpy as jnp

import flax.linen as nn

from deepspeed_tpu.moe.sharded_moe import MOELayer
from deepspeed_tpu.parallel.topology import get_topology
from deepspeed_tpu.utils.logging import log_dist


class MoE(nn.Module):
    """Mixture-of-experts layer wrapping an expert module.

    ``expert`` is any flax module mapping ``[..., hidden] -> [..., hidden]``
    and accepting a ``deterministic`` kwarg (e.g. the model's MLP block).
    """

    hidden_size: int
    expert: nn.Module
    num_experts: int = 1
    ep_size: int = 1
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    use_residual: bool = False
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    # dispatch/combine route ("dense"|"sorted") + the sorted route's
    # permutation kernel ("auto"|"xla"|"pallas"), as MOELayer takes them
    route: str = "sorted"
    route_kernel: str = "auto"
    # k >= 2: renormalise the k chosen experts' weights (Mixtral, the
    # reference top-2) or keep the softmax values (OLMoE)
    norm_topk_prob: bool = True
    # ``(first, count)``: the experts this device holds of ``num_experts``
    # (``MOELayer.experts_held``); ``expert`` is then a bank of ``count``
    experts_held: Optional[Tuple[int, int]] = None
    # the gate's scoring and a scale on the chosen experts' weights
    # (``TopKGate``), and a module every token also passes through, added to
    # the routed result (``MOELayer.shared_expert``)
    score: str = "softmax"
    routed_scale: float = 1.0
    shared_expert: Optional[nn.Module] = None

    def setup(self):
        if self.noisy_gate_policy not in (None, 'None', 'Jitter', 'RSample'):
            raise ValueError(f"Unsupported noisy_gate_policy: {self.noisy_gate_policy}")
        if not 1 <= self.k <= self.num_experts:
            raise ValueError(f"top-k gating needs 1 <= k <= num_experts "
                             f"(got k={self.k}, num_experts={self.num_experts})")
        if self.num_experts % self.ep_size != 0:
            raise ValueError(f"num_experts ({self.num_experts}) must be divisible by "
                             f"ep_size ({self.ep_size})")
        topo = get_topology()
        if topo is not None and self.ep_size > 1 and topo.expert_parallel_size not in (1, self.ep_size):
            log_dist(f"MoE ep_size={self.ep_size} differs from mesh expert axis "
                     f"{topo.expert_parallel_size}; the mesh axis wins on TPU")
        self.deepspeed_moe = MOELayer(
            expert=self.expert,
            model_dim=self.hidden_size,
            num_experts=self.num_experts,
            k=self.k,
            capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            min_capacity=self.min_capacity,
            noisy_gate_policy=None if self.noisy_gate_policy == 'None' else self.noisy_gate_policy,
            drop_tokens=self.drop_tokens,
            use_rts=self.use_rts,
            route=self.route,
            route_kernel=self.route_kernel,
            norm_topk_prob=self.norm_topk_prob,
            experts_held=self.experts_held,
            score=self.score,
            routed_scale=self.routed_scale,
            shared_expert=self.shared_expert,
        )
        if self.use_residual:
            # PR-MoE (reference layer.py:70-77): dense MLP alongside the MoE
            # path, mixed by a learned 2-way coefficient
            self.mlp = _ResidualExpertWrapper(expert=self.expert)
            self.coefficient = nn.Dense(2, use_bias=True, dtype=jnp.float32, name="coefficient")

    def __call__(self, hidden_states, used_token=None, deterministic: bool = True,
                 router_input=None):
        """Returns ``(output, l_aux, exp_counts)`` (reference ``layer.py:98``).
        ``router_input`` (shaped like ``hidden_states``): what the gate reads
        where that is not what the experts read; None, the same tensor."""
        output, l_aux, exp_counts = self.deepspeed_moe(hidden_states, used_token, deterministic,
                                                       router_input=router_input)
        if self.use_residual:
            mlp_out = self.mlp(hidden_states, deterministic=deterministic)
            coef = self.coefficient(hidden_states.astype(jnp.float32))
            coef = nn.softmax(coef, axis=-1).astype(output.dtype)
            output = output * coef[..., 0:1] + mlp_out * coef[..., 1:2]
        return output, l_aux, exp_counts


class _ResidualExpertWrapper(nn.Module):
    """A fresh (non-expert-parallel) copy of the expert module for the
    PR-MoE residual path."""

    expert: nn.Module

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        return self.expert.copy(name="residual_mlp")(x, deterministic=deterministic)
