"""LLaMA family — RMSNorm + RoPE + SwiGLU + GQA decoder; Mixtral, Qwen2,
OLMoE, SmallThinker, Laguna and Ouro are configurations of it
(judged config ladder includes LLaMA-7B ZeRO-3 + ZeRO++, BASELINE.md; the
reference supports LLaMA through kernel injection,
``module_inject/containers/llama.py``).

TPU-first notes, same conventions as ``models/gpt2.py``:
* logical axis names via ``nn.with_logical_partitioning`` drive the ZeRO
  planner (fsdp/TP shardings are derived, never hand-sliced);
* attention goes through the pluggable backend seam (xla/flash/ring);
* a flax ``cache`` collection implements incremental decoding (the role of
  the reference's KV-cache workspace,
  ``csrc/transformer/inference/includes/inference_context.h``): the decode
  cache every family shares (``models/common.py`` ``DecodeCache``), so the
  server's per-slot int8 cache serves this family as it serves GPT-2; RoPE
  rotates by each slot's own write position.
* layers need not be alike (``LlamaConfig``'s layouts; Laguna-XS.2 uses them
  all): a window or full attention a layer, its own number of query heads
  over the same key heads, its own RoPE (plain, or YaRN over part of a head:
  :class:`RopeKind`), a sigmoid gate a head, leading dense layers before
  sparse ones, sigmoid-routed experts with a shared one. A window layer's
  decode cache may be a RING of the window and a chunk
  (``window_ring``; ``DecodeCache(ring=True)``), and a decode attention may
  walk its stored pool a block at a time, grouped-query, the int8 codes as
  they lie (``models/common.py`` ``cached_attention``): a window layer's
  always does, and so does every serving decode tick
  (``DecodeCache.attend_tick``, as in every family). On a TPU a decode tick's
  walk is one kernel that reads each slot as far as that slot goes
  (``ops/pallas/pool_decode.py``).
* the stack may be LOOPED (``loop_passes``; Ouro-2.6B runs its 48 layers four
  times): the layers are applied several times a token over one set of
  weights, the final norm after every pass, two more norms a layer on the
  sublayers' outputs (``sandwich_norm``), an exit gate whose distribution over
  the passes is returned where asked. The passes are one loop on the device
  (``nn.scan`` over the pass's number: parameters broadcast, the cache
  carried), so a program holds the layers' bodies once, and a layer keeps a
  cache a PASS under its one set of leaves (``DecodeCache(parts=...)``: the
  passes side by side on the pools' head axis). One pass is the plain stack:
  the same tree, the same cache, the same programs.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.common import (DecodeCache, cached_attention, config_from,
                                         decode_key_block, dense_init as _init,
                                         normalize_padding_mask, ring_mask, rms_norm,
                                         window_ring_positions)  # noqa: F401  (re-export)
from deepspeed_tpu.ops.transformer.attention import dot_product_attention


EXPERT_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclasses.dataclass(frozen=True)
class RopeKind:
    """How one kind of layer rotates its queries and keys: RoPE at ``theta``
    over the first ``rotary_share`` of a head's dimensions (the rest pass
    unrotated), plain or, with ``yarn_factor``, YaRN (Peng et al. 2023): each
    frequency a blend of the plain one and that one over ``yarn_factor``, by
    how many turns it makes over ``original_positions`` (``beta_fast`` turns
    and more: plain; ``beta_slow`` and fewer: divided), and the cosine and
    sine times ``attention_factor`` (None: ``0.1 ln(yarn_factor) + 1``)."""
    theta: float = 10000.0
    rotary_share: float = 1.0
    yarn_factor: Optional[float] = None
    original_positions: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @property
    def plain(self) -> bool:
        return self.yarn_factor is None and self.rotary_share == 1.0


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32  # < num_attention_heads → GQA
    # width of one head; None = ``hidden_size // num_attention_heads`` (set in
    # ``__post_init__``). SmallThinker publishes 128 with 28 heads over a
    # hidden size of 2,560: heads x head_dim need not be the hidden size
    head_dim: Optional[int] = None
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    # jax.checkpoint policy name + selective application (same semantics as
    # GPT2Config.remat_policy/remat_every; runtime/activation_checkpointing)
    remat_policy: Optional[str] = None
    remat_every: int = 1
    attention_backend: str = "xla"
    # flash-backend block geometry / bwd policy override, as a spec string
    # (models/common.py attention_geometry_kwargs); None = resolve via
    # env/config/autotune layers
    attention_blocks: Optional[str] = None
    attention_bias: bool = False  # Qwen2-style biased q/k/v projections
    # OLMoE-style QK-norm: an RMSNorm over the whole projected q and the
    # whole projected k (all heads at once), before the split into heads
    # and RoPE
    qk_norm: bool = False
    # positions the decode cache holds per sequence; None = the context,
    # ``max_position_embeddings`` (RoPE needs no table, so a server may
    # reserve less than the context for each slot)
    decode_cache_len: Optional[int] = None
    # Mistral-style sliding-window attention: each token attends the last
    # ``sliding_window`` positions. In training and prefill the flash kernel
    # skips out-of-window blocks (O(L*window)). In decode a window layer walks
    # its stored pool under the window's mask (:func:`cached_attention`): a
    # pool of every position or, with ``window_ring``, a ring.
    sliding_window: Optional[int] = None
    # positions of a window layer's decode cache, a RING written at ``position
    # mod window_ring`` (``models/common.py`` ``RING_KV_LEAVES``): at least the
    # window less one and the longest chunk a call writes
    # (:func:`window_ring_positions`). None: as many as a full layer's pool,
    # which never wraps
    window_ring: Optional[int] = None
    # key positions one step of a decode attention's walk over its stored pool
    # takes (``models/common.py`` ``cached_attention``: grouped-query, the int8
    # codes read as they are stored, bounded by the live lengths): a step of
    # XLA's loop, and on a TPU the block a grid step of a decode tick's kernel
    # brings into VMEM (``ops/pallas/pool_decode.py``). For a serving decode
    # tick and a window layer's decode a size and nothing else: they walk
    # whatever it is (None: ``models/common.py`` ``decode_key_block`` of the
    # pool). What a full layer's CHUNK and lockstep decode take still follows
    # it, as it did (set: ``_walk``, as Laguna's; None: the whole pool to the
    # attention backend): the chunk's read is ROADMAP S3 (b)'s other half
    decode_key_block: Optional[int] = None
    # per layer, as SmallThinker publishes them: 1 = this layer attends its
    # window (``sliding_window``), 0 = full causal attention; 1 = RoPE on
    # this layer's queries and keys, 0 = no positional encoding (NoPE).
    # None: every layer alike (the window wherever one is set, RoPE always)
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    # query heads by layer (Laguna: 48 on full layers, 64 on window layers,
    # over the same 8 key heads): ``q_proj``, ``o_proj`` and the gate then
    # differ in shape from layer to layer. None: ``num_attention_heads`` all
    num_attention_heads_layout: Optional[Tuple[int, ...]] = None
    # RoPE by the kind of layer: a full layer's and a window layer's
    # (:class:`RopeKind`, or its fields as a dict). None: plain at ``rope_theta``
    rope_full: Optional[RopeKind] = None
    rope_window: Optional[RopeKind] = None
    # None, or "headwise": one sigmoid gate a head from the layer's normed
    # input, ``o_h <- sigmoid(x W_g)_h o_h`` before ``o_proj``
    attention_gate: Optional[str] = None
    # a serving chunk's logits are made for each sequence's LAST REAL token
    # alone, [B, 1, V] (the prefill program keeps no other: over 100,352 rows a
    # whole chunk's logits are a fifth of a prefill tick and 3.3 GB). Needs
    # ``counts_real_tokens``; a verify step needs every position's, so the
    # scheduler refuses speculation over such a model by name
    head_last_fed_only: bool = False
    # >0: when called with ``labels=``, compute the loss via the chunked
    # fused LM head (models/common.py fused_lm_head_loss) — never
    # materializes [B, L, V] logits (32k-152k vocabs make that the
    # dominant buffer); the value is tokens per chunk
    fused_head_loss_chunk: int = 0
    # Mixtral-style sparse MoE FFN (reference GPT-MoE wiring; MoE every
    # moe_layer_freq-th layer replaces the SwiGLU MLP with experts)
    moe_num_experts: int = 0  # 0 = dense
    moe_layer_freq: int = 1   # Mixtral: every layer
    # leading layers that keep the dense SwiGLU MLP of ``intermediate_size``
    # whatever ``moe_layer_freq`` says (Laguna: one)
    moe_first_dense: int = 0
    # width of one expert (None: ``intermediate_size``) and of the one shared
    # SwiGLU expert every token also passes through (0: none)
    moe_intermediate_size: Optional[int] = None
    moe_shared_intermediate_size: int = 0
    # the router's score ("softmax" over the experts | "sigmoid", each alone)
    # and a scale on the chosen experts' weights (``TopKGate``)
    moe_score: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_k: int = 2            # Mixtral: top-2; any k <= experts (OLMoE: 8 of 64)
    # renormalise the k chosen experts' weights (Mixtral) or keep their
    # softmax values (OLMoE: ``norm_topk_prob`` false)
    moe_norm_topk_prob: bool = True
    # False: no token is ever dropped (OLMoE). The experts are then one
    # bank and a tick's token copies are grouped by expert with no padding
    # (moe/sharded_moe.py); the capacity factors are not used
    moe_drop_tokens: bool = True
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: float = 2.0  # serving must not under-provision vs training
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    # the experts' gated activation: "silu" (SwiGLU) or "relu" (SmallThinker's
    # ReGLU, ``relu(gate) * up``); the dense MLP stays SwiGLU
    moe_activation: str = "silu"
    # the router reads the layer's INPUT, before the input norm and before
    # attention (SmallThinker's pre-attention router), not the normed
    # post-attention state the experts read
    moe_router_before_attention: bool = False
    # ``(first, count)``: the experts this device holds of ``moe_num_experts``
    # (``MOELayer.experts_held``; drop-free route on one device). The router
    # keeps every output; the bank holds ``count`` experts
    moe_experts_held: Optional[Tuple[int, int]] = None
    # dispatch/combine route ("dense"|"sorted") and the sorted route's
    # permutation kernel ("auto"|"xla"|"pallas"); the engine's "moe" config
    # block lands here
    moe_route: str = "sorted"
    moe_route_kernel: str = "auto"
    # a LOOPED stack (Ouro, arXiv:2510.25741): the whole stack of layers is
    # applied ``loop_passes`` times over ONE set of weights, the final norm
    # after every pass, pass ``t``'s output the input of pass ``t + 1`` and the
    # head on the last pass's. A pass's keys and values come from that pass's
    # stream, so a layer keeps a cache a pass (``models/common.py``
    # ``DecodeCache`` ``parts``: the passes side by side on the pools' head
    # axis, under the layer's one set of leaves), and every pass of a token
    # writes at the token's one position. An exit gate (a ``hidden -> 1`` linear
    # with bias, a sigmoid a position a pass) gives the exit distribution over
    # the passes, returned where ``return_exit_pdf`` asks; every position runs
    # every pass (the published ``early_exit_threshold`` of 1). 1: a plain stack
    loop_passes: int = 1
    # two more RMSNorms a layer, one on each sublayer's OUTPUT before it joins
    # the stream (``x + norm(attn(norm(x)))``, ``x + norm(mlp(norm(x)))``): what
    # keeps a looped stack's recurrence stable
    sandwich_norm: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_attention_heads)
        for name in ("rope_full", "rope_window"):
            if isinstance(getattr(self, name), dict):
                object.__setattr__(self, name, RopeKind(**getattr(self, name)))
        if self.attention_gate not in (None, "headwise"):
            raise NotImplementedError(f"attention gate {self.attention_gate!r}: only "
                                      f"headwise is built")
        for name in ("moe_experts_held", "sliding_window_layout", "rope_layout",
                     "num_attention_heads_layout"):
            given = getattr(self, name)
            if given is None:
                continue
            # a list from JSON: keep the config hashable
            object.__setattr__(self, name, tuple(int(v) for v in given))
            if name.endswith("_layout") and len(given) != self.num_hidden_layers:
                raise ValueError(f"{name} names {len(given)} layers, the model has "
                                 f"{self.num_hidden_layers}")
        if self.moe_activation not in EXPERT_ACTIVATIONS:
            raise ValueError(f"moe_activation must be one of {sorted(EXPERT_ACTIVATIONS)}, "
                             f"got {self.moe_activation!r}")
        if self.loop_passes < 1:
            raise ValueError(f"loop_passes must be at least 1, got {self.loop_passes}")
        if self.loop_passes > 1 and self.window_ring is not None:
            raise NotImplementedError("a looped stack over window rings: a ring a pass is not built")

    def window_of(self, layer: int) -> Optional[int]:
        """The window layer ``layer`` attends, None where it attends all."""
        if self.sliding_window_layout is not None and not self.sliding_window_layout[layer]:
            return None
        return self.sliding_window

    def rope_on(self, layer: int) -> bool:
        return self.rope_layout is None or bool(self.rope_layout[layer])

    def rope_of(self, layer: int) -> Optional[RopeKind]:
        """How layer ``layer`` rotates, None where it does not."""
        if not self.rope_on(layer):
            return None
        kind = self.rope_full if self.window_of(layer) is None else self.rope_window
        return kind or RopeKind(theta=self.rope_theta)

    def heads_of(self, layer: int) -> int:
        """Query heads of layer ``layer``."""
        if self.num_attention_heads_layout is None:
            return self.num_attention_heads
        return self.num_attention_heads_layout[layer]

    @property
    def counts_real_tokens(self) -> bool:
        """Whether a serving tick is told which of its tokens are real
        (``chunk_length``, ``models/common.py`` ``LENGTH_LEAVES``): padding and
        parked slots then route to no expert, write no ring and bound no walk."""
        return self.moe_experts_held is not None or self.decode_key_block is not None \
            or self.window_ring is not None


LLAMA_CONFIGS = {
    "test": dict(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128),
    "160m": dict(hidden_size=768, intermediate_size=2048, num_hidden_layers=12,
                 num_attention_heads=12, num_key_value_heads=12),
    "1b": dict(hidden_size=2048, intermediate_size=5504, num_hidden_layers=24,
               num_attention_heads=16, num_key_value_heads=16),
    "7b": dict(hidden_size=4096, intermediate_size=11008, num_hidden_layers=32,
               num_attention_heads=32, num_key_value_heads=32),
    # Mistral-7B: llama blocks + GQA(8) + 14336 MLP + 4096 sliding window
    "mistral-7b": dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                       num_hidden_layers=32, num_attention_heads=32,
                       num_key_value_heads=8, max_position_embeddings=32768,
                       sliding_window=4096),
    "13b": dict(hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
                num_attention_heads=40, num_key_value_heads=40),
    # Mixtral-8x7B shape: llama blocks, top-2 of 8 SwiGLU experts per layer
    "mixtral-8x7b": dict(hidden_size=4096, intermediate_size=14336, num_hidden_layers=32,
                         num_attention_heads=32, num_key_value_heads=8,
                         max_position_embeddings=4096, rope_theta=1e6,
                         moe_num_experts=8, moe_k=2),
    "mixtral-test": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                         max_position_embeddings=128, moe_num_experts=4, moe_k=2),
    # OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct): every FFN is 64 SwiGLU
    # experts of width 1024, top-8 with the softmax values as weights, no
    # token dropped; QK-norm; 1.3 B of 6.9 B parameters active per token
    "olmoe-1b-7b": dict(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=16,
                        max_position_embeddings=4096, rms_norm_eps=1e-5, rope_theta=10000.0,
                        qk_norm=True, moe_num_experts=64, moe_k=8, moe_norm_topk_prob=False,
                        moe_drop_tokens=False),
    "olmoe-test": dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                       num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                       max_position_embeddings=128, rms_norm_eps=1e-5, qk_norm=True,
                       moe_num_experts=8, moe_k=2, moe_norm_topk_prob=False,
                       moe_drop_tokens=False),
    # SmallThinker-21BA3B (PowerInfer/SmallThinker-21BA3B-Instruct): 52 layers,
    # every fourth (l % 4 == 0) full causal attention with no positional
    # encoding, the others RoPE and a window of 4,096; 28 query heads of 128
    # on 4 key heads over a hidden size of 2,560; every FFN 64 ReGLU experts
    # of width 768, top-6 renormalised, routed from the layer's input
    "smallthinker-21b-a3b": dict(
        vocab_size=151936, hidden_size=2560, intermediate_size=768, num_hidden_layers=52,
        num_attention_heads=28, num_key_value_heads=4, head_dim=128,
        max_position_embeddings=16384, rms_norm_eps=1e-6, rope_theta=1.5e6,
        sliding_window=4096,
        sliding_window_layout=tuple(int(i % 4 != 0) for i in range(52)),
        rope_layout=tuple(int(i % 4 != 0) for i in range(52)),
        moe_num_experts=64, moe_k=6, moe_norm_topk_prob=True, moe_drop_tokens=False,
        moe_activation="relu", moe_router_before_attention=True, moe_aux_loss_coef=0.0),
    # two periods of it at a size the CPU runs: heads x head_dim != hidden
    "smallthinker-test": dict(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        max_position_embeddings=32, rms_norm_eps=1e-6, rope_theta=1.5e6, sliding_window=8,
        sliding_window_layout=tuple(int(i % 4 != 0) for i in range(8)),
        rope_layout=tuple(int(i % 4 != 0) for i in range(8)),
        moe_num_experts=8, moe_k=3, moe_norm_topk_prob=True, moe_drop_tokens=False,
        moe_activation="relu", moe_router_before_attention=True, moe_aux_loss_coef=0.0),
    # Laguna-XS.2 (poolside/Laguna-XS.2) at a size the CPU runs: a leading
    # dense layer and one period [full, window x 3], then full; 6 query heads
    # on full layers and 8 on window layers over 2 key heads; a gate a head;
    # YaRN on half a head on full layers, plain RoPE on window layers; 8
    # sigmoid-routed experts, top-2 renormalised x 2.5, and a shared one, all
    # held; a window of 8 in a ring of 16
    "laguna-test": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=5,
        num_attention_heads=6, num_key_value_heads=2, head_dim=16,
        num_attention_heads_layout=(6, 8, 8, 8, 6), max_position_embeddings=128,
        rms_norm_eps=1e-6, sliding_window=8, sliding_window_layout=(0, 1, 1, 1, 0),
        window_ring=16, decode_key_block=16, attention_gate="headwise",
        rope_full=RopeKind(theta=500000.0, rotary_share=0.5, yarn_factor=64.0,
                           original_positions=16, beta_fast=64.0, beta_slow=1.0,
                           attention_factor=1.4158883083359672),
        rope_window=RopeKind(theta=10000.0),
        moe_num_experts=8, moe_k=2, moe_first_dense=1, moe_intermediate_size=32,
        moe_shared_intermediate_size=32, moe_score="sigmoid", moe_routed_scale=2.5,
        moe_norm_topk_prob=True, moe_drop_tokens=False, moe_experts_held=(0, 8),
        moe_aux_loss_coef=0.0),
    # Ouro-2.6B (ByteDance/Ouro-2.6B, arXiv:2510.25741): 48 MHA layers with
    # sandwich norms, the whole stack run four times over one set of weights,
    # the final norm after every pass, an exit gate, a cache a pass
    "ouro-2.6b": dict(vocab_size=49152, hidden_size=2048, intermediate_size=5632,
                      num_hidden_layers=48, num_attention_heads=16, num_key_value_heads=16,
                      max_position_embeddings=65536, rms_norm_eps=1e-6, rope_theta=1e6,
                      loop_passes=4, sandwich_norm=True),
    # the same at a size the CPU runs: three layers three times
    "ouro-test": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=128, rms_norm_eps=1e-6, rope_theta=1e6,
                      loop_passes=3, sandwich_norm=True),
    # Qwen2 family: llama architecture + biased q/k/v projections
    "qwen2-7b": dict(vocab_size=152064, hidden_size=3584, intermediate_size=18944,
                     num_hidden_layers=28, num_attention_heads=28, num_key_value_heads=4,
                     max_position_embeddings=32768, rope_theta=1e6, attention_bias=True),
}


def get_llama_config(name: str, **overrides) -> LlamaConfig:
    return config_from(LLAMA_CONFIGS, LlamaConfig, name, **overrides)


class RMSNorm(nn.Module):
    """Root-mean-square norm (reference fused kernel
    ``csrc/transformer/inference/csrc/rms_norm.cu``; XLA fuses this)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = self.param("weight", nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
                       (x.shape[-1],), cfg.param_dtype)
        w = w.value if isinstance(w, nn.meta.AxisMetadata) else w
        return rms_norm(x, w, cfg.rms_norm_eps, cfg.dtype)


def rotary_embedding(x, positions, theta: float = 10000.0):
    """Apply RoPE to ``x`` [B, L, H, D] at ``positions`` [B, L]
    (reference fused kernel ``apply_rotary_pos_emb.cu``; half-split layout)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta**(jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, L, D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, L, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_frequencies(kind: RopeKind, head_dim: int):
    """``(inverse frequencies [rotated dims / 2] float32, the factor on cosine
    and sine)`` of ``kind`` for heads of ``head_dim``, made on the host in
    float64. YaRN: dimension pair ``i`` turns ``original_positions theta^(-2i/D)
    / 2 pi`` times over the original context; pairs from the one that makes
    ``beta_fast`` turns down keep the plain frequency, pairs up to the one that
    makes ``beta_slow`` take it over ``yarn_factor``, and those between a
    linear blend of the two (the pairs' numbers rounded outwards)."""
    dim = int(head_dim * kind.rotary_share)
    plain = kind.theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if kind.yarn_factor is None:
        return plain.astype(np.float32), 1.0

    def pair_that_turns(turns):
        return dim * np.log(kind.original_positions / (turns * 2 * np.pi)) / (2 * np.log(kind.theta))

    low = max(np.floor(pair_that_turns(kind.beta_fast)), 0)
    high = min(np.ceil(pair_that_turns(kind.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    blended = plain / kind.yarn_factor * ramp + plain * (1 - ramp)
    factor = kind.attention_factor if kind.attention_factor is not None \
        else 0.1 * np.log(kind.yarn_factor) + 1.0
    return blended.astype(np.float32), float(factor)


def rotate(x, positions, kind: Optional[RopeKind]):
    """``x`` [B, L, H, D] rotated at ``positions`` [B, L] as ``kind`` says
    (None: not at all); half-split pairs inside the rotated dimensions."""
    if kind is None:
        return x
    if kind.plain:
        return rotary_embedding(x, positions, kind.theta)
    with jax.named_scope("rope_yarn" if kind.yarn_factor is not None else "rope_partial"):
        inv_freq, factor = rope_frequencies(kind, x.shape[-1])
        dim = 2 * inv_freq.shape[0]
        angles = positions[..., None].astype(jnp.float32) * inv_freq
        cos = (jnp.cos(angles) * factor)[:, :, None, :]
        sin = (jnp.sin(angles) * factor)[:, :, None, :]
        turned, passed = x[..., :dim].astype(jnp.float32), x[..., dim:]
        x1, x2 = jnp.split(turned, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return jnp.concatenate([out.astype(x.dtype), passed], axis=-1)


class LlamaAttention(nn.Module):
    """GQA attention with RoPE and an optional decode cache. ``layer``
    picks this layer's window, its query heads and how it rotates
    (``LlamaConfig``'s layouts); every layer is alike where the configuration
    has none."""

    config: LlamaConfig
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions=None, *, decode: bool = False, attention_mask=None,
                 fed=None, loop_pass=None):
        """``loop_pass`` (a looped stack's, a traced scalar): which pass of
        ``loop_passes`` this call is, so which of the layer's caches it
        writes and reads."""
        cfg = self.config
        b, l, _ = x.shape
        heads = cfg.heads_of(self.layer)
        n_rep = heads // cfg.num_key_value_heads
        window = cfg.window_of(self.layer)
        kind = cfg.rope_of(self.layer)
        rope = lambda t, at: rotate(t, at, kind)  # noqa: E731

        def proj(heads, name):
            # q/k/v projections only (o_proj is built separately, always
            # bias-free); Qwen2-style configs bias these three
            return nn.DenseGeneral(features=(heads, cfg.head_dim), axis=-1,
                                   use_bias=cfg.attention_bias,
                                   dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                                   kernel_init=nn.with_logical_partitioning(_init(), ("embed", "heads", "kv")),
                                   bias_init=nn.with_logical_partitioning(nn.initializers.zeros,
                                                                          ("heads", "kv")),
                                   name=name)

        q = proj(heads, "q_proj")(x)
        k = proj(cfg.num_key_value_heads, "k_proj")(x)
        v = proj(cfg.num_key_value_heads, "v_proj")(x)
        if cfg.qk_norm:
            def whole(t, name):
                # over every head's features at once, as published
                flat = RMSNorm(cfg, name=name)(t.reshape(b, l, -1))
                return flat.reshape(t.shape)
            q, k = whole(q, "q_norm"), whole(k, "k_norm")

        causal = True
        decode_lengths = None
        # attention_mask: [B, L] 0/1 padding mask (or a pre-broadcast boolean
        # mask). In decode mode L must span the cache (max_position_embeddings).
        mask = normalize_padding_mask(attention_mask)
        # a decode that walks the stored pool (:func:`cached_attention`): a
        # window layer's always, a full layer's where the configuration says
        walks = decode and (window is not None or cfg.decode_key_block is not None)
        # static-shape KV cache, lockstep or per serving slot, fp or int8
        # (models/common.py DecodeCache; the cache handed in decides)
        cache = DecodeCache(self, b, cfg.decode_cache_len or cfg.max_position_embeddings,
                            cfg.num_key_value_heads, cfg.head_dim, k.dtype,
                            **_cache_of_pass(cfg, loop_pass)) \
            if decode and not walks else None
        if walks:
            out = self._walk(q, k, v, positions, rope, window, fed, mask, loop_pass)
        elif decode and cache.ticks(l):
            # a serving decode tick reads its pool where it lies
            if mask is not None:
                raise NotImplementedError("a padding mask over a serving decode tick, which reads "
                                          "its stored pool (DecodeCache.attend_tick): not built")
            at = cache.positions(l) if positions is None else positions
            with jax.named_scope("attn_full"):
                out = cache.attend_tick(rope(q, at), rope(k, at), v, fed=fed,
                                        q_pos=None if positions is None else positions[:, -1])
        else:
            if decode:
                given = positions is not None
                if not given:
                    positions = cache.positions(l)
                q, k = rope(q, positions), rope(k, positions)
                k, v, decode_lengths = cache.append(k, v, q.dtype)
                if given:
                    # per-sequence live lengths (positions may differ per batch
                    # row); the backend derives causal validity over cache slots
                    # from them — flash's decode kernel additionally skips dead
                    # KV blocks' DMA. Any caller padding mask rides alongside
                    # (flash falls back to XLA when both are present).
                    decode_lengths = positions[:, -1] + 1
                causal = False
            else:
                if positions is None:
                    positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
                q, k = rope(q, positions), rope(k, positions)

            # GQA: the flash kernels read key head ``head // n_rep`` themselves
            # (training and prefill); every other path gets the heads repeated
            if n_rep > 1 and (decode or cfg.attention_backend != "flash"):
                k = jnp.repeat(k, n_rep, axis=2)
                v = jnp.repeat(v, n_rep, axis=2)

            if window is not None and cfg.attention_backend not in ("flash", "xla"):
                # silently ignoring the window would change the model's math
                raise ValueError(f"sliding_window is supported by the flash/xla attention "
                                 f"backends, not {cfg.attention_backend!r}")
            from deepspeed_tpu.models.common import attention_geometry_kwargs
            with jax.named_scope("attn_window" if window is not None else "attn_full"):
                out = dot_product_attention(q, k, v, backend=cfg.attention_backend, causal=causal,
                                            mask=mask, decode_lengths=decode_lengths,
                                            window=window, **attention_geometry_kwargs(cfg))
        if cfg.attention_gate:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(nn.Dense(
                    features=heads, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_logical_partitioning(_init(), ("embed", "heads")),
                    name="gate_proj")(x))
                out = out * gate[..., None].astype(out.dtype)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=nn.with_logical_partitioning(_init(), ("heads", "kv", "embed")),
                               name="o_proj")(out)

    def _walk(self, q, k, v, positions, rope, window, fed, mask, loop_pass=None):
        """Decode by :func:`cached_attention`: the new keys and values written
        (a window layer's into its ring where the configuration sizes one),
        then the stored pool walked under the window's mask. Leaves ``kv_reads``
        (``models/common.py`` ``KV_READS``) in a serving cache."""
        cfg = self.config
        b, l = q.shape[:2]
        if mask is not None:
            raise NotImplementedError("a padding mask over a decode that walks its stored pool "
                                      "(decode_key_block, a window layer): not built")
        extent = cfg.decode_cache_len or cfg.max_position_embeddings
        ring = window is not None and cfg.window_ring is not None
        places = cfg.window_ring if ring else extent
        if ring and l > places - (window - 1):
            raise ValueError(
                f"a call of {l} tokens over a ring of {places} positions overwrites what its "
                f"first query's window of {window} still reads: window_ring must be "
                f"window_ring_positions(window, the longest chunk)")
        cache = DecodeCache(self, b, places, cfg.num_key_value_heads, cfg.head_dim, k.dtype,
                            ring=ring, **_cache_of_pass(cfg, loop_pass))
        if positions is None:
            positions = cache.positions(l)
        if fed is None:
            fed = jnp.full((b,), l, jnp.int32)
        q, k = rope(q, positions), rope(k, positions)
        cache.write(k, v, live=fed > 0)
        with jax.named_scope("attn_window" if window is not None else "attn_full"):
            out, read = cached_attention(
                q, *cache.stored(), positions, fed, window=window or places,
                block=cfg.decode_key_block or decode_key_block(
                    cfg.num_key_value_heads, cfg.head_dim, places), rows=cache.slots,
                **cache._part)
        ends = jnp.where(fed > 0, jnp.minimum(positions[:, 0] + fed, extent), 0)
        if window is None:
            counts = {"kv_full_positions_read": read, "kv_full_positions_live": ends.sum()}
        else:
            # of a sequence's positions, the ones inside some real query's window
            counts = {"kv_ring_positions_read": read,
                      "kv_ring_positions_live": jnp.minimum(ends, fed + window - 1).sum(),
                      "kv_ring_bytes_written": fed.sum() * 2 * cfg.num_key_value_heads * (
                          cfg.head_dim * cache.key.value.dtype.itemsize
                          + (k.dtype.itemsize if cache.quantized else 0))}
        cache.count_reads(**counts)
        return out


def _cache_of_pass(cfg: LlamaConfig, loop_pass) -> dict:
    """What tells a layer's ``DecodeCache`` that it is one of a looped stack's:
    a cache a pass, and which pass this call is."""
    return {} if cfg.loop_passes == 1 else {"parts": cfg.loop_passes, "part": loop_pass}


class LlamaMLP(nn.Module):
    """SwiGLU MLP (reference fused GEGLU/gated-mlp inference kernels,
    ``csrc/transformer/inference/csrc/gelu.cu`` fused_gemm_gelu family).

    ``num_experts`` > 0 makes it a bank: every kernel carries a leading
    expert axis, under the paths and shapes ``moe.Experts``' vmap gives a
    single MLP's. A bank takes the capacity layout ``[..., E, C, M]`` or,
    with ``group_sizes`` [E], rows ``[R, M]`` sorted by expert with no
    padding between the groups (the drop-free sorted route)."""

    config: LlamaConfig
    num_experts: int = 0
    width: Optional[int] = None     # None: ``intermediate_size``

    @nn.compact
    def __call__(self, x, deterministic: bool = True, group_sizes=None, impl: str = "xla"):
        cfg = self.config
        width = self.width or cfg.intermediate_size
        if not self.num_experts:
            def dense(feat, names, name):
                return nn.Dense(features=feat, use_bias=False, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                kernel_init=nn.with_logical_partitioning(_init(), names),
                                name=name)

            gate = dense(width, ("embed", "mlp"), "gate_proj")(x)
            up = dense(width, ("embed", "mlp"), "up_proj")(x)
            return dense(cfg.hidden_size, ("mlp", "embed"), "down_proj")(jax.nn.silu(gate) * up)

        def kernel(shape, names, name):
            return ExpertKernel((self.num_experts,) + shape, ("expert",) + names,
                                cfg.param_dtype, name=name)().astype(cfg.dtype)

        w_gate = kernel((cfg.hidden_size, width), ("embed", "mlp"), "gate_proj")
        w_up = kernel((cfg.hidden_size, width), ("embed", "mlp"), "up_proj")
        w_down = kernel((width, cfg.hidden_size), ("mlp", "embed"), "down_proj")
        x = x.astype(cfg.dtype)
        if group_sizes is None:
            dot = lambda t, w: jnp.einsum("...eci,eio->...eco", t, w)  # noqa: E731
        else:
            from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul
            dot = lambda t, w: grouped_matmul(t, w, group_sizes, impl=impl)  # noqa: E731
        act = EXPERT_ACTIVATIONS[cfg.moe_activation]
        return dot(act(dot(x, w_gate)) * dot(x, w_up), w_down)


class ExpertKernel(nn.Module):
    """A projection kernel of every expert, ``[E, in, out]``, at the param
    path ``<name>/kernel`` where a vmapped ``nn.Dense`` keeps it."""

    shape: tuple
    names: tuple
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        w = self.param("kernel", nn.with_logical_partitioning(_init(), self.names),
                       self.shape, self.param_dtype)
        return w.value if isinstance(w, nn.meta.AxisMetadata) else w


class LlamaDecoderLayer(nn.Module):
    config: LlamaConfig
    use_moe: bool = False
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions=None, decode: bool = False, attention_mask=None,
                 deterministic: bool = True, fed=None, loop_pass=None):
        """``fed`` [batch] int32 (a serving tick of a model that
        ``counts_real_tokens``): how many of each sequence's tokens are real.
        ``loop_pass``: which pass of a looped stack this call is."""
        cfg = self.config
        # a sublayer's output, normed before it joins the stream where the
        # configuration sandwiches its sublayers
        after = (lambda name, t: RMSNorm(cfg, name=name)(t)) if cfg.sandwich_norm \
            else (lambda name, t: t)
        # the pre-attention router reads the stream as it enters the layer
        router_input = x if self.use_moe and cfg.moe_router_before_attention else None
        x = x + after("input_layernorm_2", LlamaAttention(cfg, self.layer, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(x), positions, decode=decode,
            attention_mask=attention_mask, fed=fed,
            **({} if loop_pass is None else {"loop_pass": loop_pass})))
        h = RMSNorm(cfg, name="post_attention_layernorm")(x)
        if self.use_moe:
            from deepspeed_tpu.moe import MoE
            # drop-free routing groups rows by expert, which takes a bank:
            # of every expert, or of the ones this device holds
            bank = 0 if cfg.moe_drop_tokens else cfg.moe_num_experts
            if cfg.moe_experts_held is not None:
                bank = cfg.moe_experts_held[1]
            used = None if fed is None else (
                jnp.arange(x.shape[1])[None, :] < fed[:, None]).reshape(-1)
            shared = (LlamaMLP(cfg, width=cfg.moe_shared_intermediate_size)
                      if cfg.moe_shared_intermediate_size else None)
            moe_out, l_aux, _ = MoE(hidden_size=cfg.hidden_size,
                                    expert=LlamaMLP(cfg, num_experts=bank,
                                                    width=cfg.moe_intermediate_size),
                                    num_experts=cfg.moe_num_experts,
                                    k=cfg.moe_k,
                                    capacity_factor=cfg.moe_capacity_factor,
                                    eval_capacity_factor=cfg.moe_eval_capacity_factor,
                                    min_capacity=cfg.moe_min_capacity,
                                    drop_tokens=cfg.moe_drop_tokens,
                                    norm_topk_prob=cfg.moe_norm_topk_prob,
                                    route=cfg.moe_route,
                                    route_kernel=cfg.moe_route_kernel,
                                    experts_held=cfg.moe_experts_held,
                                    score=cfg.moe_score, routed_scale=cfg.moe_routed_scale,
                                    shared_expert=shared,
                                    name="moe")(h, used_token=used, deterministic=deterministic,
                                                router_input=router_input)
            return x + after("post_attention_layernorm_2", moe_out), l_aux
        return (x + after("post_attention_layernorm_2", LlamaMLP(cfg, name="mlp")(h)),
                jnp.zeros([], jnp.float32))


from deepspeed_tpu.models.common import init_cache  # noqa: E402  (re-export)


class LlamaForCausalLM(nn.Module):
    """LLaMA with an untied LM head. Returns logits [B, L, V].

    ``decode=True`` runs incrementally against the flax ``cache`` collection
    (pass ``mutable=["cache"]`` to ``apply``).
    """

    # offload_param streaming: these block subtrees self-stream inside
    # their remat region (param_offload.stream_block_params); the engine
    # top-streams only the remaining leaves
    streamed_block_prefixes = ("layers_",)


    config: LlamaConfig

    def moe_layers(self):
        """Indices of the layers whose FFN is the expert layer."""
        cfg = self.config
        every = max(cfg.moe_layer_freq, 1)
        return [i for i in range(cfg.moe_first_dense, cfg.num_hidden_layers)
                if cfg.moe_num_experts > 0 and i % every == every - 1]

    def step_count_names(self):
        """What each expert layer counts on the device in a training step
        (``moe/sharded_moe.py`` ``HELD_COUNTS``; the engine returns them
        beside the loss): only a layer that holds a share of its experts
        counts, since only there the rows it computes are not the copies."""
        from deepspeed_tpu.moe.sharded_moe import HELD_COUNTS
        return HELD_COUNTS if self.config.moe_experts_held is not None and self.moe_layers() \
            else ()

    def step_counts(self, counted):
        """``counted``, the ``step_counts`` collection one forward pass wrote,
        as ``[layers, counts]`` int32: a row an expert layer, in layer order."""
        return jnp.stack([counted[f"layers_{i}"]["moe"]["deepspeed_moe"]["moe_rows"]
                          for i in self.moe_layers()])

    def moe_rows(self, positions: int):
        """``(routed, computed)``: expert-matmul rows one position owes over
        a forward pass (``k`` a layer), and rows the expert matmuls of one
        pass over ``positions`` positions are given, padding included: the
        grouped buffer's ``positions * k`` a layer when drop-free, else
        ``experts * capacity``. The server counts its ticks with this."""
        cfg = self.config
        layers = len(self.moe_layers())
        if not layers or cfg.moe_experts_held is not None:
            # a layer that holds a share counts its own rows, on the device
            return 0, 0
        if not cfg.moe_drop_tokens:
            per_layer = positions * cfg.moe_k
        else:
            from deepspeed_tpu.moe.sharded_moe import _gate_capacity
            per_layer = cfg.moe_num_experts * _gate_capacity(
                positions, cfg.moe_num_experts, cfg.moe_eval_capacity_factor,
                cfg.moe_min_capacity, True, cfg.moe_k)
        return cfg.moe_k * layers, per_layer * layers

    def loop_weight_bytes(self, params) -> int:
        """Weight bytes a decode tick of a looped stack streams, from the served
        tree: the layers and the final norm once a pass, the head once (the
        table is a lookup; the exit gate is not run). The server counts its
        decode ticks with this."""
        stack = outside = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            top = str(getattr(path[0], "key", path[0]))
            size = leaf.size * leaf.dtype.itemsize
            if top.startswith("layers_") or top == "norm":
                stack += size
            elif top == "lm_head":
                outside += size
        return self.config.loop_passes * stack + outside

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True, decode: bool = False,
                 positions=None, attention_mask=None, labels=None,
                 return_exit_pdf: bool = False):
        """``return_exit_pdf`` (a looped stack's): also return the exit
        distribution over the passes, [B, L, passes] float32, behind the logits."""
        cfg = self.config
        wte = self.param("embed_tokens", nn.with_logical_partitioning(_init(), ("vocab", "embed")),
                         (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        wte_value = wte.value if isinstance(wte, nn.meta.AxisMetadata) else wte
        from deepspeed_tpu.models.common import embed_lookup
        x = embed_lookup(wte_value, input_ids,
                         getattr(cfg, 'embed_onehot_grad', None), decode).astype(cfg.dtype)

        from deepspeed_tpu.models.common import constrain_activation, maybe_remat
        # residual stream stays batch-parallel over fsdp-sharded weights —
        # see constrain_activation (the ZeRO-3 weak-scaling invariant)
        x = constrain_activation(x, "batch", "length", "embed")
        moe_layers = self.moe_layers()
        fed = None
        if decode and cfg.counts_real_tokens:
            # how many of each sequence's tokens are real: a scalar in lockstep
            # ``generate`` (all real), a [slots] vector in the serving cache,
            # which the serving programs fill from their operands. Padding and
            # parked slots route to no expert, write no ring and bound no walk
            length = self.variable("cache", "chunk_length", lambda: jnp.zeros([], jnp.int32))
            if length.value.ndim:
                fed = length.value

        def stack(x, *loop_pass):
            """The layers once, each made under the module that is current:
            this one, or its stand-in inside a looped stack's scan."""
            aux_total = jnp.zeros([], jnp.float32)
            for i in range(cfg.num_hidden_layers):
                use_moe = i in moe_layers
                block_cls = maybe_remat(LlamaDecoderLayer, cfg, i, static_argnums=(3, 5),
                                        enabled=cfg.remat and not decode)
                x, l_aux = block_cls(cfg, use_moe, i, name=f"layers_{i}")(
                    x, positions, decode, attention_mask, deterministic, fed, *loop_pass)
                x = constrain_activation(x, "batch", "length", "embed")
                aux_total = aux_total + l_aux
            return x, aux_total

        exit_pdf = None
        if cfg.loop_passes == 1:
            x, aux_total = stack(x)
            if fed is not None and cfg.head_last_fed_only and x.shape[1] > 1:
                x = jnp.take_along_axis(x, (jnp.maximum(fed, 1) - 1)[:, None, None], axis=1)
            x = RMSNorm(cfg, name="norm")(x)
        else:
            x, aux_total, exit_pdf = self._looped(stack, x, return_exit_pdf)
            if fed is not None and cfg.head_last_fed_only and x.shape[1] > 1:
                x = jnp.take_along_axis(x, (jnp.maximum(fed, 1) - 1)[:, None, None], axis=1)
        if labels is not None and cfg.fused_head_loss_chunk > 0:
            # chunked fused head on the [E, V] Dense kernel — same param
            # path ("lm_head"/"kernel") as the unfused branch, so
            # checkpoints and HF converters are unaffected (shift/aux
            # policy lives in fused_head_loss_output, shared across
            # families)
            from deepspeed_tpu.models.common import UntiedHeadKernel, fused_head_loss_output
            kernel = UntiedHeadKernel(cfg.hidden_size, cfg.vocab_size,
                                      cfg.param_dtype, name="lm_head")()
            return fused_head_loss_output(x, kernel.astype(cfg.dtype), labels,
                                          aux_total, deterministic, cfg,
                                          vocab_major=False)
        # logits at compute dtype: the loss reduces in fp32 (PERF.md #2)
        logits = nn.Dense(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype,
                          kernel_init=nn.with_logical_partitioning(_init(), ("embed", "vocab")),
                          name="lm_head")(x)
        if return_exit_pdf:
            if exit_pdf is None:
                raise ValueError("return_exit_pdf: a stack of one pass has no exit gate")
            return logits, exit_pdf
        if cfg.moe_num_experts > 0:
            return logits, aux_total * cfg.moe_aux_loss_coef
        return logits

    def _looped(self, stack, x, want_pdf: bool):
        """``x`` through ``loop_passes`` passes of ``stack`` over one set of
        weights, the final norm after every pass: ``(h_T, the passes' summed
        aux loss, the exit distribution [B, L, passes] or None)``. The passes
        are ONE loop on the device (``nn.scan`` over the pass's number: the
        parameters broadcast, the cache carried and written in place), so a
        program holds the layers' bodies once however many passes run them;
        the variables are made by one pass run plainly (``init``)."""
        cfg = self.config

        def one_pass(mdl, carry, t):
            x, aux_total = carry
            with jax.named_scope("loop_pass"):
                x, l_aux = stack(x, t)
            with jax.named_scope("loop_norm"):
                x = RMSNorm(cfg, name="norm")(x)
            gate = None
            if want_pdf or mdl.is_initializing():
                # lambda_t = sigmoid(w_g . h_t + b_g), one scalar a position
                gate = jax.nn.sigmoid(nn.Dense(
                    features=1, use_bias=True, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_logical_partitioning(_init(), ("embed", None)),
                    name="exit_gate")(x)[..., 0])
            return (x, aux_total + l_aux), gate

        carry = (x, jnp.zeros([], jnp.float32))
        if self.is_initializing():
            (x, aux_total), _ = one_pass(self, carry, jnp.zeros([], jnp.int32))
            return x, aux_total, None
        (x, aux_total), gates = nn.scan(
            one_pass, variable_broadcast="params", variable_carry="cache",
            split_rngs={"params": False}, length=cfg.loop_passes,
            check_constancy_invariants=False)(self, carry, jnp.arange(cfg.loop_passes))
        if not want_pdf:
            return x, aux_total, None
        # p_t = lambda_t prod_{j<t} (1 - lambda_j), the last pass takes what is left
        stay = jnp.cumprod(1.0 - gates, axis=0)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        pdf = jnp.concatenate([(gates * before)[:-1], before[-1:]])
        return x, aux_total, jnp.moveaxis(pdf, 0, -1)
