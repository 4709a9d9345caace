"""GPT-2 family — the flagship decoder-only model (config ladder:
125M → 350M → 760M → XL-1.5B, BASELINE.md).

TPU-first design notes:
* every parameter carries t5x-style logical axis names via
  ``nn.with_partitioning`` so the ZeRO planner
  (``deepspeed_tpu.parallel.sharding``) can derive tensor-parallel and
  fsdp shardings declaratively — the role the reference fills with
  Megatron mpu slicing + ``zero.Init`` (``partition_parameters.py``);
* attention goes through the pluggable backend seam
  (``deepspeed_tpu.ops.transformer.attention``) so the XLA reference path
  and the Pallas flash kernel are interchangeable;
* ``remat`` wraps each block with ``jax.checkpoint`` — the analog of the
  reference's activation checkpointing (``runtime/activation_checkpointing``).
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.common import embed_lookup
from deepspeed_tpu.ops.transformer.attention import dot_product_attention

Dtype = Any


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.float32  # compute dtype; params stay in param_dtype
    param_dtype: Any = jnp.float32
    remat: bool = False
    # jax.checkpoint policy name (runtime/activation_checkpointing: e.g.
    # "dots_saveable" keeps matmul outputs, None = full recompute) and
    # selective application (checkpoint every Nth block; reference
    # ``number_checkpoints`` semantics)
    remat_policy: Optional[str] = None
    remat_every: int = 1
    attention_backend: str = "xla"
    # flash-backend block geometry / bwd policy override, as a spec string
    # ("block_q=256,block_k=512,policy=recompute", see models/common.py
    # attention_geometry_kwargs); None = resolve via env/config/autotune
    attention_blocks: Optional[str] = None
    # QKV projection as ONE fused [E,3,H,D] GEMM (default, the historical
    # program) vs three sliced GEMMs over the SAME parameter — a program-
    # shape dimension graft-search enumerates (analysis/search.py; engine
    # "program" config block). Checkpoint layout is identical either way.
    attn_fused_qkv: bool = True
    # attention-output projection contracting (heads, kv) directly off the
    # [B,L,H,D] attention output (default) vs an explicit [B,L,H*D]
    # reshape then a 2D GEMM — same parameter, different program shape
    attn_fused_out: bool = True
    # backward of the token-embedding gather as a one-hot matmul instead of
    # a scatter-add. Default ON: scatter serializes on TPU (measured +10%
    # with the matmul form, PERF.md r3 session 3) AND the scatter-add's
    # batch-sharded→embed-sharded update reshard is the "Involuntary full
    # rematerialization" GSPMD warns about on expert/fsdp meshes — the
    # einsum backward partitions cleanly (contraction psum)
    embed_onehot_grad: bool = True
    # >0: when called with ``labels=``, compute the loss via the chunked
    # fused LM head (models/common.py fused_lm_head_loss) — never
    # materializes [B, L, V] logits; the value is tokens per chunk
    fused_head_loss_chunk: int = 0
    # progressive layer drop (arXiv:2010.13369; reference
    # ``runtime/progressive_layer_drop.py``): when True and the engine
    # passes ``pld_theta``, each sublayer is stochastically skipped at
    # train time with depth-scaled keep probability
    progressive_layer_drop: bool = False
    # MoE (reference GPT-MoE configs: every other layer is an MoE FFN)
    moe_num_experts: int = 0  # 0 = dense model
    moe_layer_freq: int = 2  # MoE every Nth block (reference expert-interval)
    moe_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: float = 2.0
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_noisy_gate_policy: Optional[str] = None
    moe_use_residual: bool = False
    moe_drop_tokens: bool = True
    moe_use_rts: bool = True
    # dispatch/combine route ("dense"|"sorted") and the sorted route's
    # permutation kernel ("auto"|"xla"|"pallas"); the engine's "moe" config
    # block lands here
    moe_route: str = "sorted"
    moe_route_kernel: str = "auto"
    # graft-quant-serve: served weight dtype this module instance was BUILT
    # for ("int8"|"int4"). None (training, lockstep generate) keeps the fp
    # projections. Set by the serving scheduler from its ServingConfig: the
    # param tree's code layout must match what the projections statically
    # declare (int4 halves the contraction axis)
    serve_weight_dtype: Optional[str] = None

    @property
    def head_dim(self):
        return self.n_embd // self.n_head


GPT2_CONFIGS = {
    # tiny config for unit tests (vocab multiple of 8 for mesh divisibility)
    "test": dict(vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4),
    "125m": dict(n_embd=768, n_layer=12, n_head=12),
    "350m": dict(n_embd=1024, n_layer=24, n_head=16),
    "760m": dict(n_embd=1536, n_layer=24, n_head=16),
    "xl": dict(n_embd=1600, n_layer=48, n_head=25),
}


def get_gpt2_config(name: str, **overrides) -> GPT2Config:
    from deepspeed_tpu.models.common import config_from
    return config_from(GPT2_CONFIGS, GPT2Config, name, **overrides)


def _dense_init(scale=0.02):
    from deepspeed_tpu.models.common import dense_init
    return dense_init(scale)


_QUANT_BITS = {"int8": 8, "int4": 4}


def _serve_quant_mode(module, cfg) -> str:
    """Resolved weight dtype for a projection: quantized only when the
    module was built for it (``serve_weight_dtype`` set) AND this scope's
    scales ride along in the ``"quant"`` collection — leaves the skip list
    (``ops/quantizer/weights.py``) keeps fp stay fp automatically."""
    swd = getattr(cfg, "serve_weight_dtype", None)
    if swd in (None, "fp"):
        return "fp"
    from deepspeed_tpu.ops.quantizer.weights import quant_bits
    quant_bits(swd)  # validates the choice
    if not module.has_variable("quant", "kernel_scale"):
        return "fp"
    return swd


class QKVProj(nn.Module):
    """QKV projection over ONE fused ``[E, 3, H, D]`` parameter (the exact
    layout/init ``nn.DenseGeneral(features=(3, H, D))`` declared here
    historically, so checkpoints are unchanged) with two program forms:
    ``attn_fused_qkv=True`` emits the single fused GEMM; ``False`` emits
    three sliced GEMMs — identical math, different program shape for the
    scheduler/partitioner, the fusion dimension graft-search prices."""

    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        unbox = lambda p: p.value if isinstance(p, nn.meta.AxisMetadata) else p
        wq = _serve_quant_mode(self, cfg)
        kshape = (cfg.n_embd, 3, cfg.n_head, cfg.head_dim)
        if wq == "int4":
            kshape = (cfg.n_embd // 2,) + kshape[1:]  # packed contraction axis
        kernel = unbox(self.param(
            "kernel", nn.with_logical_partitioning(_dense_init(), ("embed", None, "heads", "kv")),
            kshape, cfg.param_dtype))
        bias = unbox(self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros, (None, "heads", "kv")),
            (3, cfg.n_head, cfg.head_dim), cfg.param_dtype))
        x = x.astype(cfg.dtype)
        bias = bias.astype(cfg.dtype)
        if wq != "fp":
            # dequant fused into the GEMM; always the fused program form —
            # the quantized serving program is one GEMM per projection
            from deepspeed_tpu.ops.pallas.quant_matmul import quant_dense_general
            qkv = quant_dense_general(x, kernel,
                                      self.get_variable("quant", "kernel_scale"),
                                      bits=_QUANT_BITS[wq], n_contract=1)
            qkv = qkv + jnp.reshape(bias, (1,) * (qkv.ndim - bias.ndim) + bias.shape)
            return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        kernel = kernel.astype(cfg.dtype)
        contract = ((x.ndim - 1,), (0,))
        if cfg.attn_fused_qkv:
            qkv = jax.lax.dot_general(x, kernel, (contract, ((), ())))
            qkv = qkv + jnp.reshape(bias, (1,) * (qkv.ndim - bias.ndim) + bias.shape)
            return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        outs = []
        for i in range(3):
            o = jax.lax.dot_general(x, kernel[:, i], (contract, ((), ())))
            outs.append(o + jnp.reshape(bias[i], (1,) * (o.ndim - 2) + bias[i].shape))
        return tuple(outs)


class AttnOutProj(nn.Module):
    """Attention-output projection over the ``[H, D, E]`` parameter
    ``nn.DenseGeneral(features=E, axis=(-2, -1))`` declared here
    historically. ``attn_fused_out=True`` contracts (heads, kv) directly
    off the ``[B, L, H, D]`` attention output; ``False`` reshapes to
    ``[B, L, H*D]`` first and runs a 2D GEMM — same parameter, the second
    fusion dimension graft-search prices."""

    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        unbox = lambda p: p.value if isinstance(p, nn.meta.AxisMetadata) else p
        wq = _serve_quant_mode(self, cfg)
        kshape = (cfg.n_head, cfg.head_dim, cfg.n_embd)
        if wq == "int4":
            kshape = (cfg.n_head, cfg.head_dim // 2, cfg.n_embd)
        kernel = unbox(self.param(
            "kernel", nn.with_logical_partitioning(_dense_init(), ("heads", "kv", "embed")),
            kshape, cfg.param_dtype))
        bias = unbox(self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
            (cfg.n_embd,), cfg.param_dtype))
        x = x.astype(cfg.dtype)
        bias = bias.astype(cfg.dtype)
        if wq != "fp":
            from deepspeed_tpu.ops.pallas.quant_matmul import quant_dense_general
            out = quant_dense_general(x, kernel,
                                      self.get_variable("quant", "kernel_scale"),
                                      bits=_QUANT_BITS[wq], n_contract=2)
            return out + bias
        kernel = kernel.astype(cfg.dtype)
        if cfg.attn_fused_out:
            out = jax.lax.dot_general(
                x, kernel, (((x.ndim - 2, x.ndim - 1), (0, 1)), ((), ())))
        else:
            flat = x.reshape(x.shape[:-2] + (cfg.n_head * cfg.head_dim,))
            out = jax.lax.dot_general(
                flat, kernel.reshape(cfg.n_head * cfg.head_dim, cfg.n_embd),
                (((flat.ndim - 1,), (0,)), ((), ())))
        return out + bias


class SelfAttention(nn.Module):
    config: GPT2Config
    decode: bool = False

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.config
        q, k, v = QKVProj(cfg, name="c_attn")(x)
        dropout_rng = None
        if not deterministic and cfg.dropout > 0.0:
            dropout_rng = self.make_rng("dropout")
        causal, decode_lengths, cache = True, None, None
        if self.decode:
            # incremental decoding against the static-shape KV cache every
            # family shares (models/common.py DecodeCache: lockstep or
            # per-slot writes, fp or int8 pools, decided by the cache handed in)
            from deepspeed_tpu.models.common import DecodeCache
            cache = DecodeCache(self, x.shape[0], cfg.n_positions, cfg.n_head, cfg.head_dim,
                                k.dtype)
        if cache is not None and cache.ticks(x.shape[1]):
            # a serving decode tick reads its pool where it lies
            if dropout_rng is not None:
                raise NotImplementedError("attention dropout over a serving decode tick, which "
                                          "reads its stored pool (DecodeCache.attend_tick): "
                                          "not built")
            attn_out = cache.attend_tick(q, k, v)
        else:
            if cache is not None:
                k, v, decode_lengths = cache.append(k, v, q.dtype)
                causal = False
            from deepspeed_tpu.models.common import attention_geometry_kwargs
            attn_out = dot_product_attention(q,
                                             k,
                                             v,
                                             backend=cfg.attention_backend,
                                             causal=causal,
                                             decode_lengths=decode_lengths,
                                             dropout_rate=0.0 if deterministic else cfg.dropout,
                                             dropout_rng=dropout_rng,
                                             **attention_geometry_kwargs(cfg))
        out = AttnOutProj(cfg, name="c_proj")(attn_out)
        if not deterministic and cfg.dropout > 0.0:
            out = nn.Dropout(rate=cfg.dropout)(out, deterministic=False)
        return out


class QuantDense(nn.Module):
    """Drop-in for ``nn.Dense`` (identical param names/shapes/init/
    partitioning, so checkpoints and shardings are unchanged) that adds
    the quantized serving path: when built with ``serve_weight_dtype``
    and this scope carries quant scales, the kernel arrives as int8/int4
    codes and dequant fuses into the GEMM
    (``ops/pallas/quant_matmul.py``)."""

    config: GPT2Config
    features: int
    kernel_axes: Any = ("embed", "mlp")
    bias_axes: Any = ("mlp",)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        unbox = lambda p: p.value if isinstance(p, nn.meta.AxisMetadata) else p
        wq = _serve_quant_mode(self, cfg)
        in_features = x.shape[-1]
        kshape = (in_features // 2 if wq == "int4" else in_features, self.features)
        kernel = unbox(self.param(
            "kernel", nn.with_logical_partitioning(_dense_init(), self.kernel_axes),
            kshape, cfg.param_dtype))
        bias = unbox(self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros, self.bias_axes),
            (self.features,), cfg.param_dtype))
        x = x.astype(cfg.dtype)
        bias = bias.astype(cfg.dtype)
        if wq != "fp":
            from deepspeed_tpu.ops.pallas.quant_matmul import quant_dense_general
            out = quant_dense_general(x, kernel,
                                      self.get_variable("quant", "kernel_scale"),
                                      bits=_QUANT_BITS[wq], n_contract=1)
            return out + bias
        out = jax.lax.dot_general(x, kernel.astype(cfg.dtype),
                                  (((x.ndim - 1,), (0,)), ((), ())))
        return out + bias


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.config
        h = QuantDense(cfg, features=4 * cfg.n_embd,
                       kernel_axes=("embed", "mlp"), bias_axes=("mlp",),
                       name="c_fc")(x)
        h = jax.nn.gelu(h, approximate=True)
        h = QuantDense(cfg, features=cfg.n_embd,
                       kernel_axes=("mlp", "embed"), bias_axes=("embed",),
                       name="c_proj")(h)
        if not deterministic and cfg.dropout > 0.0:
            h = nn.Dropout(rate=cfg.dropout)(h, deterministic=False)
        return h


class LayerNorm(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                            dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
                            bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)))(x)


class Block(nn.Module):
    config: GPT2Config
    use_moe: bool = False
    decode: bool = False

    def _pld_gate(self, branch, keep):
        from deepspeed_tpu.models.common import pld_gate
        return pld_gate(self, branch, keep)

    @nn.compact
    def __call__(self, x, deterministic: bool = True, pld_keep=None):
        # deterministic is positional (not kw-only) so nn.remat can mark it
        # static (static_argnums below)
        cfg = self.config
        keep = None if (deterministic or pld_keep is None) else pld_keep
        attn_out = SelfAttention(cfg, self.decode, name="attn")(LayerNorm(cfg, name="ln_1")(x),
                                                                deterministic=deterministic)
        gated_attn, _ = self._pld_gate(attn_out, keep)
        x = x + gated_attn
        h = LayerNorm(cfg, name="ln_2")(x)
        if self.use_moe:
            from deepspeed_tpu.moe import MoE
            moe_out, l_aux, _ = MoE(hidden_size=cfg.n_embd,
                                    expert=MLP(cfg),
                                    num_experts=cfg.moe_num_experts,
                                    k=cfg.moe_k,
                                    capacity_factor=cfg.moe_capacity_factor,
                                    eval_capacity_factor=cfg.moe_eval_capacity_factor,
                                    min_capacity=cfg.moe_min_capacity,
                                    use_residual=cfg.moe_use_residual,
                                    noisy_gate_policy=cfg.moe_noisy_gate_policy,
                                    drop_tokens=cfg.moe_drop_tokens,
                                    use_rts=cfg.moe_use_rts,
                                    route=cfg.moe_route,
                                    route_kernel=cfg.moe_route_kernel,
                                    name="moe")(h, deterministic=deterministic)
            gated_moe, b = self._pld_gate(moe_out, keep)
            x = x + gated_moe
            if b is not None:
                # a dropped expert layer must not push balancing gradients
                # into its router either
                l_aux = jnp.where(b, l_aux, jnp.zeros_like(l_aux))
            return x, l_aux
        gated_mlp, _ = self._pld_gate(MLP(cfg, name="mlp")(h, deterministic=deterministic), keep)
        x = x + gated_mlp
        return x, jnp.zeros([], jnp.float32)


class GPT2LMHeadModel(nn.Module):
    """GPT-2 with tied-embedding LM head. Returns logits [B, L, V]."""

    config: GPT2Config
    # offload_param streaming: h_* blocks self-stream inside their remat
    # region (maybe_remat); the engine top-streams only the rest (wte/wpe/
    # ln_f), keeping per-layer device copies out of the remat residuals
    streamed_block_prefixes = ("h_",)

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True, decode: bool = False,
                 labels=None, pld_theta=None):
        cfg = self.config
        wte = self.param("wte", nn.with_logical_partitioning(_dense_init(), ("vocab", "embed")),
                         (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param("wpe", nn.with_logical_partitioning(_dense_init(0.01), (None, "embed")),
                         (cfg.n_positions, cfg.n_embd), cfg.param_dtype)
        wte_value = wte.value if isinstance(wte, nn.meta.AxisMetadata) else wte
        wpe_value = wpe.value if isinstance(wpe, nn.meta.AxisMetadata) else wpe

        _, seq_len = input_ids.shape
        x = embed_lookup(wte_value, input_ids, cfg.embed_onehot_grad, decode).astype(cfg.dtype)
        if decode:
            # position offset for wpe; advances in lockstep with each
            # attention layer's cache_index (same increment per call — flax
            # offers no clean cross-module read, so the counter is duplicated)
            pos_idx = self.variable("cache", "position_index", lambda: jnp.zeros([], jnp.int32))
            if pos_idx.value.ndim:
                # per-slot serving cache: [B] positions (clip keeps parked
                # slots' sentinel positions in-table; their rows are dead)
                positions = jnp.clip(pos_idx.value[:, None] + jnp.arange(seq_len)[None, :],
                                     0, cfg.n_positions - 1)
                x = x + jnp.take(wpe_value, positions, axis=0).astype(cfg.dtype)
            else:
                positions = pos_idx.value + jnp.arange(seq_len)
                x = x + jnp.take(wpe_value, positions, axis=0).astype(cfg.dtype)[None]
            pos_idx.value = pos_idx.value + seq_len
        else:
            x = x + wpe_value[:seq_len].astype(cfg.dtype)
        if not deterministic and cfg.dropout > 0.0:
            x = nn.Dropout(rate=cfg.dropout)(x, deterministic=False)

        from deepspeed_tpu.models.common import constrain_activation, maybe_remat
        # pin the residual stream to batch-parallel sharding: without this
        # GSPMD may replicate the batch over fsdp-sharded (ZeRO-3) weights
        # and all-reduce per-layer contractions — per-chip bytes that grow
        # with the mesh (see constrain_activation)
        x = constrain_activation(x, "batch", "length", "embed")
        aux_total = jnp.zeros([], jnp.float32)
        use_pld = cfg.progressive_layer_drop and pld_theta is not None and not deterministic
        for i in range(cfg.n_layer):
            use_moe = cfg.moe_num_experts > 0 and (i % cfg.moe_layer_freq == cfg.moe_layer_freq - 1)
            block_cls = maybe_remat(Block, cfg, i, static_argnums=(2,))
            # PLD depth scaling (paper eq. 6): deeper blocks drop more often
            keep_i = 1.0 - (i + 1) / cfg.n_layer * (1.0 - pld_theta) if use_pld else None
            x, l_aux = block_cls(cfg, use_moe, decode, name=f"h_{i}")(x, deterministic, keep_i)
            x = constrain_activation(x, "batch", "length", "embed")
            aux_total = aux_total + l_aux
        x = LayerNorm(cfg, name="ln_f")(x)
        if labels is not None and cfg.fused_head_loss_chunk > 0:
            # chunked fused head: next-token NLL straight from hidden
            # states, no [B,L,V] logits buffer (shift/aux policy lives in
            # fused_head_loss_output, shared across families)
            from deepspeed_tpu.models.common import fused_head_loss_output
            return fused_head_loss_output(x, wte_value.astype(cfg.dtype), labels,
                                          aux_total, deterministic, cfg,
                                          vocab_major=True)
        # tied LM head. Logits stay at the COMPUTE dtype: [B,L,V] is the
        # single largest activation (824MB fp32 at bs4/seq1024/GPT-2 vocab)
        # and the loss does its softmax reductions in fp32 anyway
        # (cross_entropy_loss) — bf16 logits halve the dominant HBM traffic
        # of the step (PERF.md hypothesis #2)
        logits = jnp.einsum("ble,ve->blv", x, wte_value.astype(cfg.dtype),
                            preferred_element_type=cfg.dtype)
        if cfg.moe_num_experts > 0:
            return logits, aux_total * cfg.moe_aux_loss_coef
        return logits


# ---------------------------------------------------------------------------
# Pipeline-parallel layer adapters (reference expresses GPT-2 for pipelining
# as a LayerSpec list — e.g. Megatron's GPT2ModelPipe; here the specs feed
# deepspeed_tpu.runtime.pipe.module.PipelineModule)
# ---------------------------------------------------------------------------
class GPT2EmbedPipe(nn.Module):
    """Token+position embedding; ``attend`` is the tied LM head."""

    config: GPT2Config

    def setup(self):
        cfg = self.config
        self.wte = self.param("wte", nn.with_logical_partitioning(_dense_init(), ("vocab", "embed")),
                              (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        self.wpe = self.param("wpe", nn.with_logical_partitioning(_dense_init(0.01), (None, "embed")),
                              (cfg.n_positions, cfg.n_embd), cfg.param_dtype)

    def __call__(self, input_ids):
        cfg = self.config
        wte = self.wte.value if isinstance(self.wte, nn.meta.AxisMetadata) else self.wte
        wpe = self.wpe.value if isinstance(self.wpe, nn.meta.AxisMetadata) else self.wpe
        x = embed_lookup(wte, input_ids, cfg.embed_onehot_grad).astype(cfg.dtype)
        return x + wpe[:input_ids.shape[-1]].astype(cfg.dtype)

    def attend(self, x):
        wte = self.wte.value if isinstance(self.wte, nn.meta.AxisMetadata) else self.wte
        return jnp.einsum("...le,ve->...lv", x, wte.astype(self.config.dtype),
                          preferred_element_type=self.config.dtype)


class GPT2BlockPipe(nn.Module):
    """One transformer block with a plain ``x -> x`` signature (pipeline
    stages stream activations only; deterministic — pipeline dropout would
    need per-stage rng plumbing)."""

    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        out, _ = Block(self.config, name="block")(x, True)
        return out


class GPT2LNPipe(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        return LayerNorm(self.config, name="ln_f")(x)


def gpt2_pipe_layers(config: GPT2Config):
    """The LayerSpec list for a pipelined GPT-2 (embedding tied to the LM
    head, reference ``TiedLayerSpec`` semantics)."""
    from deepspeed_tpu.runtime.pipe.module import LayerSpec, TiedLayerSpec

    if config.moe_num_experts > 0:
        raise ValueError("MoE blocks are not supported in the pipelined GPT-2: the pipeline "
                         "stage body is deterministic and drops the aux loss. Combine "
                         "expert parallelism with ZeRO/TP instead (expert mesh axis).")

    return [
        TiedLayerSpec("embed", GPT2EmbedPipe, config, tied_weight_attr="wte"),
        *[LayerSpec(GPT2BlockPipe, config) for _ in range(config.n_layer)],
        LayerSpec(GPT2LNPipe, config),
        TiedLayerSpec("embed", GPT2EmbedPipe, config, tied_weight_attr="wte",
                      forward_fn=lambda m, x: m.attend(x)),
    ]


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Mean token cross-entropy with label masking (fp32 accumulation).

    The fp32 upcast feeds ONLY the logsumexp reduction so XLA fuses the
    convert into the reduce; the label gather reads the compute-dtype
    logits and upcasts the [B,L] result — bit-identical (f32(bf16) is
    exact) but avoids materializing [B,L,V] in fp32, the single largest
    allocation of the train step (3 GiB at mb16/seq1024/GPT-2 vocab).
    """
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    label_logit = jnp.take_along_axis(
        logits, safe_labels[..., None], axis=-1)[..., 0].astype(jnp.float32)
    nll = (logz - label_logit) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)
