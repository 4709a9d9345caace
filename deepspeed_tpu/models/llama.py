"""LLaMA family — RMSNorm + RoPE + SwiGLU + GQA decoder; Mixtral, Qwen2
and OLMoE are configurations of it
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
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.common import (DecodeCache, config_from, dense_init as _init,
                                         normalize_padding_mask, rms_norm)
from deepspeed_tpu.ops.transformer.attention import dot_product_attention


EXPERT_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


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
    # ``sliding_window`` positions. Training/prefill only — the flash
    # kernel skips out-of-window blocks (O(L*window)). Decode keeps no ring
    # and applies no window: it raises by name where the cache is longer
    # than a window layer's window, and otherwise the whole cache lies
    # inside it.
    sliding_window: Optional[int] = None
    # per layer, as SmallThinker publishes them: 1 = this layer attends its
    # window (``sliding_window``), 0 = full causal attention; 1 = RoPE on
    # this layer's queries and keys, 0 = no positional encoding (NoPE).
    # None: every layer alike (the window wherever one is set, RoPE always)
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    # >0: when called with ``labels=``, compute the loss via the chunked
    # fused LM head (models/common.py fused_lm_head_loss) — never
    # materializes [B, L, V] logits (32k-152k vocabs make that the
    # dominant buffer); the value is tokens per chunk
    fused_head_loss_chunk: int = 0
    # Mixtral-style sparse MoE FFN (reference GPT-MoE wiring; MoE every
    # moe_layer_freq-th layer replaces the SwiGLU MLP with experts)
    moe_num_experts: int = 0  # 0 = dense
    moe_layer_freq: int = 1   # Mixtral: every layer
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

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.num_attention_heads)
        for name in ("moe_experts_held", "sliding_window_layout", "rope_layout"):
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

    def window_of(self, layer: int) -> Optional[int]:
        """The window layer ``layer`` attends, None where it attends all."""
        if self.sliding_window_layout is not None and not self.sliding_window_layout[layer]:
            return None
        return self.sliding_window

    def rope_on(self, layer: int) -> bool:
        return self.rope_layout is None or bool(self.rope_layout[layer])


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


class LlamaAttention(nn.Module):
    """GQA attention with RoPE and an optional decode cache. ``layer``
    picks this layer's window and whether it rotates (``LlamaConfig``'s two
    layouts); every layer is alike where the configuration has none."""

    config: LlamaConfig
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions=None, *, decode: bool = False, attention_mask=None):
        cfg = self.config
        b, l, _ = x.shape
        n_rep = cfg.num_attention_heads // cfg.num_key_value_heads
        window = cfg.window_of(self.layer)
        rope = (lambda t, at: rotary_embedding(t, at, cfg.rope_theta)) \
            if cfg.rope_on(self.layer) else (lambda t, at: t)

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

        q = proj(cfg.num_attention_heads, "q_proj")(x)
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
        if decode:
            # static-shape KV cache, lockstep or per serving slot, fp or int8
            # (models/common.py DecodeCache; the cache handed in decides)
            cache_len = cfg.decode_cache_len or cfg.max_position_embeddings
            if window is not None and cache_len > window:
                raise NotImplementedError(
                    f"decode over a cache of {cache_len} positions with sliding_window "
                    f"{window} (layer {self.layer}): the decode path keeps no ring and "
                    f"applies no window, so it would attend positions the layer must not "
                    f"see; set decode_cache_len <= sliding_window or serve without decode")
            cache = DecodeCache(self, b, cache_len,
                                cfg.num_key_value_heads, cfg.head_dim, k.dtype)
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
                                        window=window if not decode else None,
                                        **attention_geometry_kwargs(cfg))
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=nn.with_logical_partitioning(_init(), ("heads", "kv", "embed")),
                               name="o_proj")(out)


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

    @nn.compact
    def __call__(self, x, deterministic: bool = True, group_sizes=None, impl: str = "xla"):
        cfg = self.config
        if not self.num_experts:
            def dense(feat, names, name):
                return nn.Dense(features=feat, use_bias=False, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                kernel_init=nn.with_logical_partitioning(_init(), names),
                                name=name)

            gate = dense(cfg.intermediate_size, ("embed", "mlp"), "gate_proj")(x)
            up = dense(cfg.intermediate_size, ("embed", "mlp"), "up_proj")(x)
            return dense(cfg.hidden_size, ("mlp", "embed"), "down_proj")(jax.nn.silu(gate) * up)

        def kernel(shape, names, name):
            return ExpertKernel((self.num_experts,) + shape, ("expert",) + names,
                                cfg.param_dtype, name=name)().astype(cfg.dtype)

        w_gate = kernel((cfg.hidden_size, cfg.intermediate_size), ("embed", "mlp"), "gate_proj")
        w_up = kernel((cfg.hidden_size, cfg.intermediate_size), ("embed", "mlp"), "up_proj")
        w_down = kernel((cfg.intermediate_size, cfg.hidden_size), ("mlp", "embed"), "down_proj")
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
                 deterministic: bool = True):
        cfg = self.config
        # the pre-attention router reads the stream as it enters the layer
        router_input = x if self.use_moe and cfg.moe_router_before_attention else None
        x = x + LlamaAttention(cfg, self.layer, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(x), positions, decode=decode,
            attention_mask=attention_mask)
        h = RMSNorm(cfg, name="post_attention_layernorm")(x)
        if self.use_moe:
            from deepspeed_tpu.moe import MoE
            # drop-free routing groups rows by expert, which takes a bank:
            # of every expert, or of the ones this device holds
            bank = 0 if cfg.moe_drop_tokens else cfg.moe_num_experts
            if cfg.moe_experts_held is not None:
                bank = cfg.moe_experts_held[1]
            moe_out, l_aux, _ = MoE(hidden_size=cfg.hidden_size,
                                    expert=LlamaMLP(cfg, num_experts=bank),
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
                                    name="moe")(h, deterministic=deterministic,
                                                router_input=router_input)
            return x + moe_out, l_aux
        return x + LlamaMLP(cfg, name="mlp")(h), jnp.zeros([], jnp.float32)


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
        return [i for i in range(cfg.num_hidden_layers)
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
        if not layers:
            return 0, 0
        if not cfg.moe_drop_tokens:
            per_layer = positions * cfg.moe_k
        else:
            from deepspeed_tpu.moe.sharded_moe import _gate_capacity
            per_layer = cfg.moe_num_experts * _gate_capacity(
                positions, cfg.moe_num_experts, cfg.moe_eval_capacity_factor,
                cfg.moe_min_capacity, True, cfg.moe_k)
        return cfg.moe_k * layers, per_layer * layers

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True, decode: bool = False,
                 positions=None, attention_mask=None, labels=None):
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
        aux_total = jnp.zeros([], jnp.float32)
        moe_layers = self.moe_layers()
        for i in range(cfg.num_hidden_layers):
            use_moe = i in moe_layers
            block_cls = maybe_remat(LlamaDecoderLayer, cfg, i, static_argnums=(3, 5),
                                    enabled=cfg.remat and not decode)
            x, l_aux = block_cls(cfg, use_moe, i, name=f"layers_{i}")(
                x, positions, decode, attention_mask, deterministic)
            x = constrain_activation(x, "batch", "length", "embed")
            aux_total = aux_total + l_aux
        x = RMSNorm(cfg, name="norm")(x)
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
        if cfg.moe_num_experts > 0:
            return logits, aux_total * cfg.moe_aux_loss_coef
        return logits
