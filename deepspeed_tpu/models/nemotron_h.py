"""Nemotron-H family: a hybrid decoder whose layers are chosen, one letter a
layer, by ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` GQA
attention with **no positional embedding**, ``E`` a sparse expert layer
(``moe/sharded_moe.py``'s ``MOELayer``: a sigmoid router with a selection
bias over all the experts, non-gated ``relu(.)^2`` experts in a latent
space between two projections, one shared expert on the full hidden
state), ``-`` a dense ``relu(.)^2`` MLP. Every layer is
``h <- h + mixer(RMSNorm(h))``; an untied head follows a final RMSNorm.
(NVIDIA-Nemotron-3-Super-120B-A12B: 88 layers, 40 ``M`` / 40 ``E`` / 8 ``*``.)

What serving it asks of the decode cache, beside the attention layer's
``DecodeCache`` pools: a **recurrent state with no positions**. Each Mamba
layer keeps ``ssm_state`` [batch, heads, head dim, state] in float32 and
``conv_state``, the last ``conv_kernel - 1`` inputs of its causal
convolution (``models/common.py`` ``STATE_LEAVES``). One rule moves them,
driven by two per-slot vectors at the model's top level, which the serving
programs fill from their operands (``position_index``: where the slot
writes; ``chunk_length``: how many of the tokens it is handed are real):

* a slot that writes at position 0 starts from a zeroed state: a join
  forgets the slot's previous tenant;
* a slot handed no real token (a parked slot) gets its state and tail back
  unchanged, bit for bit;
* positions past a slot's real tokens (a short chunk is right-padded) do not
  move its state: their step size is zero, which leaves ``S`` alone, and the
  tail is gathered where the real tokens end.

More than one token goes through the chunked (SSD) form of the recurrence,
``chunk_size`` positions at a time from the carried state; one token through
the one-step recurrence. Lockstep ``generate`` (scalar index) treats every
token as real and starts from the zeroed cache it is given.

Not built: the multi-token-prediction module of the published checkpoint
(``num_nextn_predict_layers``), which never enters the language model's
logits.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.common import DecodeCache, config_from, dense_init as _init, rms_norm
from deepspeed_tpu.models.llama import ExpertKernel


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    # one letter a layer: M Mamba-2, * attention, E experts, - dense MLP
    hybrid_override_pattern: str = "MEMEMEM*EME"
    layer_norm_epsilon: float = 1e-5
    # attention (no bias, no positional embedding)
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    max_position_embeddings: int = 262144
    # positions the attention layers' decode cache holds per sequence; None =
    # the context (a server reserves far less for each slot)
    decode_cache_len: Optional[int] = None
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024      # 0: the experts read the hidden state itself
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    # (first, count): the experts this device holds of ``n_routed_experts``;
    # the router keeps every output (``MOELayer.experts_held``)
    experts_held: Optional[Tuple[int, int]] = None
    moe_route_kernel: str = "auto"
    intermediate_size: int = 2688    # a dense ``-`` layer's width
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)


NEMOTRON_H_CONFIGS = {
    # NVIDIA-Nemotron-3-Super-120B-A12B: the published sizes, all 88 layers
    "nemotron-3-super-120b-a12b": dict(
        hybrid_override_pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                                "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    # every kind of layer at sizes the CPU runs in a second; the chunk ends
    # ragged against any prompt
    "nemotron-h-test": dict(
        vocab_size=256, hidden_size=64, hybrid_override_pattern="MEM*E",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, max_position_embeddings=128,
        mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=8,
        n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=48,
        moe_latent_size=32, moe_shared_expert_intermediate_size=96,
        routed_scaling_factor=2.5, intermediate_size=96),
}


def get_nemotron_h_config(name: str, **overrides) -> NemotronHConfig:
    return config_from(NEMOTRON_H_CONFIGS, NemotronHConfig, name, **overrides)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _unboxed(p):
    return p.value if isinstance(p, nn.meta.AxisMetadata) else p


def _uniform(lo: float, hi: float, transform=lambda v: v):
    """``transform`` of a uniform draw in [lo, hi), made in float32."""
    def init(key, shape, dtype=jnp.float32):
        return transform(jax.random.uniform(key, shape, jnp.float32, lo, hi)).astype(dtype)
    return init


def _dt_bias_init(cfg):
    """The family's convention: a step drawn log-uniformly in
    [``time_step_min``, ``time_step_max``], floored, through the inverse of
    softplus, so that ``softplus(dt_bias)`` is that step."""
    def to_bias(u):
        dt = jnp.exp(u * (math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
                     + math.log(cfg.time_step_min))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return _uniform(0.0, 1.0, to_bias)


class RMSNorm(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = _unboxed(self.param("weight", nn.with_logical_partitioning(nn.initializers.ones,
                                                                        ("embed",)),
                                (x.shape[-1],), cfg.param_dtype))
        return rms_norm(x, w, cfg.layer_norm_epsilon, cfg.dtype)


def _dense(cfg, features, names, name):
    return nn.Dense(features=features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_logical_partitioning(_init(), names), name=name)


# ---------------------------------------------------------------------------
# Mamba-2: the recurrence, chunked and one step
# ---------------------------------------------------------------------------
def ssd_chunk_scan(x, dt, a, b_in, c_in, state, chunk: int):
    """The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t`` over ``l`` positions from the carried ``state``, in
    the chunked (state-space-dual) form: inside a chunk of ``chunk``
    positions the outputs are a masked, decay-weighted ``(C B^T) (dt x)``;
    between chunks only the state is carried.

    ``x`` [b, l, G, R, P] (heads as groups x heads a group), ``dt`` [b, l,
    G, R] float32 (0 where a position must not move the state), ``a`` [G, R]
    (negative), ``b_in`` / ``c_in`` [b, l, G, N], ``state`` [b, G, R, P, N]
    float32. Returns ``(y [b, l, G, R, P] float32, state)``.
    """
    bsz, l = x.shape[:2]
    n_chunks = -(-l // chunk)
    pad = n_chunks * chunk - l

    def chunks(t):
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((bsz, n_chunks, chunk) + t.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, piece):
        x, dt, bm, cm = piece
        step = dt * a                                         # [b, q, G, R], <= 0
        cum = jnp.cumsum(step, axis=1)
        xdt = (x * dt[..., None]).astype(x.dtype)
        # inside the chunk: position q reads s <= q through exp(cum_q - cum_s)
        scores = jnp.einsum("bqgn,bsgn->bgqs", cm, bm, preferred_element_type=jnp.float32)
        seg = jnp.moveaxis(cum, 1, -1)                        # [b, G, R, q]
        decay = jnp.where(lower, jnp.exp(seg[..., :, None] - seg[..., None, :]), 0.0)
        mixed = (scores[:, :, None] * decay).astype(x.dtype)  # [b, G, R, q, s]
        y = jnp.einsum("bgrqs,bsgrp->bqgrp", mixed, xdt, preferred_element_type=jnp.float32)
        # from the carried state, decayed to position q
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bqgn,bgrpn->bqgrp", cm.astype(jnp.float32), state)
        # the state at the chunk's end, written where the carried one lies:
        # the barrier orders it after the read above, else the compiler
        # copies the whole pool to keep that read's operand alive
        y, state = jax.lax.optimization_barrier((y, state))
        to_end = jnp.exp(cum[:, -1:] - cum)                   # [b, q, G, R]
        carried = jnp.exp(cum[:, -1])[..., None, None] * state
        state = carried + jnp.einsum("bqgn,bqgrp->bgrpn", bm,
                                     (xdt * to_end[..., None]).astype(x.dtype),
                                     preferred_element_type=jnp.float32)
        return state, y

    pieces = (chunks(x), chunks(dt), chunks(b_in), chunks(c_in))
    if n_chunks == 1:
        state, y = one(state, jax.tree.map(lambda t: t[0], pieces))
        y = y[None]
    else:
        state, y = jax.lax.scan(one, state, pieces)
    y = jnp.moveaxis(y, 0, 1).reshape((bsz, n_chunks * chunk) + y.shape[3:])
    return y[:, :l], state


def ssm_step(x, dt, a, b_in, c_in, state):
    """One position of the same recurrence: ``x`` [b, G, R, P], ``dt`` [b,
    G, R] float32, ``b_in`` / ``c_in`` [b, G, N], ``state`` [b, G, R, P, N]
    float32, read and written once. Returns ``(y [b, G, R, P] float32,
    state)``."""
    x, bm, cm = (t.astype(jnp.float32) for t in (x, b_in, c_in))
    state = (jnp.exp(dt * a)[..., None, None] * state
             + (x * dt[..., None])[..., None] * bm[:, :, None, None, :])
    return jnp.sum(state * cm[:, :, None, None, :], axis=-1), state


class Mamba2Mixer(nn.Module):
    """``[z, xBC, dt] = W_in x``; ``xBC <- silu(causal depthwise conv)``;
    the recurrence a head; ``y <- groupRMSNorm(y * silu(z))``; ``W_out y``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, decode: bool = False, reset=None, valid=None):
        cfg = self.config
        bsz, l, _ = x.shape
        G, N, P = cfg.n_groups, cfg.ssm_state_size, cfg.mamba_head_dim
        H, inner, width = cfg.mamba_num_heads, cfg.mamba_inner, cfg.conv_kernel
        R = H // G

        def vector(name, init, shape, names):
            return _unboxed(self.param(name, nn.with_logical_partitioning(init, names),
                                       shape, cfg.param_dtype))

        proj = _dense(cfg, 2 * inner + 2 * G * N + H, ("embed", "mlp"), "in_proj")(x)
        z, xbc, dt = jnp.split(proj, [inner, inner + cfg.conv_dim], axis=-1)
        conv_w = vector("conv1d_weight", _uniform(-1 / math.sqrt(width), 1 / math.sqrt(width)),
                        (width, cfg.conv_dim), (None, "mlp"))
        conv_b = vector("conv1d_bias", _uniform(-1 / math.sqrt(width), 1 / math.sqrt(width)),
                        (cfg.conv_dim,), ("mlp",))
        dt_bias = vector("dt_bias", _dt_bias_init(cfg), (H,), (None,)).astype(jnp.float32)
        a = -jnp.exp(vector("A_log", _uniform(1.0, 16.0, jnp.log), (H,), (None,))
                     .astype(jnp.float32)).reshape(G, R)
        d_skip = vector("D", _uniform(0.5, 1.5), (H,), (None,)).astype(jnp.float32)
        norm_w = vector("norm_weight", nn.initializers.ones, (inner,), ("mlp",))

        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)              # [b, l, H]
        # a full forward pass starts every sequence from nothing
        tail = jnp.zeros((bsz, width - 1, cfg.conv_dim), xbc.dtype)
        state = jnp.zeros((bsz, G, R, P, N), jnp.float32)
        if decode:
            tail_var = self.variable("cache", "conv_state", jnp.zeros,
                                     (bsz, width - 1, cfg.conv_dim), xbc.dtype)
            state_var = self.variable("cache", "ssm_state", jnp.zeros,
                                      (bsz, G, R, P, N), jnp.float32)
            tail, state = tail_var.value, state_var.value
            if reset is not None:
                tail = jnp.where(reset[:, None, None], jnp.zeros_like(tail), tail)
                state = jnp.where(reset[:, None, None, None, None], 0.0, state)
            if valid is not None:
                dt = jnp.where(jnp.arange(l)[None, :, None] < valid[:, None, None], dt, 0.0)

        with jax.named_scope("ssm_conv"):
            full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)   # [b, w-1+l, ch]
            conv = sum(full[:, j:j + l].astype(jnp.float32) * conv_w[j].astype(jnp.float32)
                       for j in range(width)) + conv_b.astype(jnp.float32)
            xbc = jax.nn.silu(conv).astype(cfg.dtype)
        xs, bm, cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        xs = xs.reshape(bsz, l, G, R, P)
        bm, cm = bm.reshape(bsz, l, G, N), cm.reshape(bsz, l, G, N)
        dt = dt.reshape(bsz, l, G, R)

        if decode and l == 1:
            with jax.named_scope("ssm_step"):
                y, new_state = ssm_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state)
                y = y[:, None]
        else:
            with jax.named_scope("ssm_scan"):
                y, new_state = ssd_chunk_scan(xs, dt, a, bm, cm, state, cfg.chunk_size)
        y = y + d_skip.reshape(G, R)[..., None] * xs.astype(jnp.float32)

        if decode:
            if valid is None:
                new_tail = full[:, l:]
            else:
                # the last inputs before the real tokens' end; a slot handed
                # none gets its own tail and state back
                new_tail = jax.vmap(lambda f, v: jax.lax.dynamic_slice_in_dim(f, v, width - 1, 0))(
                    full, valid)
                live = valid > 0
                new_tail = jnp.where(live[:, None, None], new_tail, tail)
                new_state = jnp.where(live[:, None, None, None, None], new_state, state)
            tail_var.value, state_var.value = new_tail.astype(tail_var.value.dtype), new_state

        # gated RMSNorm over each group's share of the inner width
        y = y.reshape(bsz, l, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(bsz, l, G, inner // G)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon)
        y = (y.reshape(bsz, l, inner) * norm_w.astype(jnp.float32)).astype(cfg.dtype)
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "out_proj")(y)


# ---------------------------------------------------------------------------
# attention without positions, the MLPs, the expert layer
# ---------------------------------------------------------------------------
#: slots a prefill tick's attention scores are built for at a time: the
#: [slots, heads, chunk, positions] float32 scores of all 64 slots at once
#: are 2.1 GB at the published sizes
ATTENTION_BATCH_BLOCK = 8


def grouped_attention(q, k, v, decode_lengths=None):
    """Causal softmax attention with grouped key/value heads and nothing
    repeated: ``q`` [b, lq, heads, d] against ``k`` / ``v`` [b, lk, kv heads,
    d], query head ``h`` reading kv head ``h // (heads / kv heads)``. With
    ``decode_lengths`` [b] the queries are the newest ``lq`` tokens of each
    sequence and ``k`` / ``v`` its whole cache (``xla_attention``'s rule)."""
    b, lq, h, d = q.shape
    lk, g = k.shape[1], k.shape[2]

    def attend(q, k, v, last):
        q = q.reshape(q.shape[0], lq, g, h // g, d)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                            preferred_element_type=jnp.float32) * d ** -0.5
        q_pos = last[:, None] - lq + jnp.arange(lq)[None, :]
        valid = jnp.arange(lk)[None, None, :] <= q_pos[:, :, None]          # [b, lq, lk]
        logits = jnp.where(valid[:, None, None], logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(q.shape[0], lq, h, d)

    last = (jnp.full((b,), lk, jnp.int32) if decode_lengths is None
            else decode_lengths.astype(jnp.int32))
    block = ATTENTION_BATCH_BLOCK
    if lq == 1 or b <= block or b % block:
        return attend(q, k, v, last)
    split = lambda t: t.reshape((b // block, block) + t.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda a: attend(*a), (split(q), split(k), split(v), split(last)))
    return out.reshape(b, lq, h, d)


class NemotronHAttention(nn.Module):
    """GQA attention, no bias, no rotation: the decode cache is
    ``DecodeCache``, as ``LlamaAttention``'s, and nothing rotates."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x, decode: bool = False):
        cfg = self.config
        bsz, l, _ = x.shape

        def proj(heads, name):
            return nn.DenseGeneral(features=(heads, cfg.head_dim), axis=-1, use_bias=False,
                                   dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                                   kernel_init=nn.with_logical_partitioning(
                                       _init(), ("embed", "heads", "kv")), name=name)

        q = proj(cfg.num_attention_heads, "q_proj")(x)
        k = proj(cfg.num_key_value_heads, "k_proj")(x)
        v = proj(cfg.num_key_value_heads, "v_proj")(x)
        decode_lengths = None
        cache = DecodeCache(self, bsz, cfg.decode_cache_len or cfg.max_position_embeddings,
                            cfg.num_key_value_heads, cfg.head_dim, k.dtype) if decode else None
        if decode and cache.ticks(l):
            # a serving decode tick reads its pool where it lies
            out = cache.attend_tick(q, k, v)
        else:
            if decode:
                k, v, decode_lengths = cache.append(k, v, q.dtype)
            out = grouped_attention(q, k, v, decode_lengths)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=nn.with_logical_partitioning(
                                   _init(), ("heads", "kv", "embed")), name="o_proj")(out)


class Relu2MLP(nn.Module):
    """``W2 relu(W1 x)^2``, not gated. ``num_experts`` > 0 makes it a bank
    that takes rows sorted by expert with their ``group_sizes`` (the
    drop-free sorted route), as ``LlamaMLP``'s bank does."""

    config: NemotronHConfig
    in_features: int
    width: int
    num_experts: int = 0

    @nn.compact
    def __call__(self, x, deterministic: bool = True, group_sizes=None, impl: str = "xla"):
        cfg = self.config
        if not self.num_experts:
            h = relu2(_dense(cfg, self.width, ("embed", "mlp"), "up_proj")(x))
            return _dense(cfg, self.in_features, ("mlp", "embed"), "down_proj")(h)
        if group_sizes is None:
            raise ValueError("a Relu2MLP bank takes rows grouped by expert (group_sizes)")
        from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

        def kernel(shape, names, name):
            return ExpertKernel((self.num_experts,) + shape, ("expert",) + names,
                                cfg.param_dtype, name=name)().astype(cfg.dtype)

        w_up = kernel((self.in_features, self.width), ("embed", "mlp"), "up_proj")
        w_down = kernel((self.width, self.in_features), ("mlp", "embed"), "down_proj")
        h = relu2(grouped_matmul(x.astype(cfg.dtype), w_up, group_sizes, impl=impl))
        return grouped_matmul(h, w_down, group_sizes, impl=impl)


def _expert_layer(cfg: NemotronHConfig, name: str):
    """``moe/``'s layer as this family configures it."""
    from deepspeed_tpu.moe.sharded_moe import MOELayer
    held = cfg.experts_held or (0, cfg.n_routed_experts)
    latent = cfg.moe_latent_size or cfg.hidden_size
    return MOELayer(
        expert=Relu2MLP(cfg, latent, cfg.moe_intermediate_size, num_experts=held[1]),
        model_dim=cfg.hidden_size, num_experts=cfg.n_routed_experts, k=cfg.num_experts_per_tok,
        drop_tokens=False, route="sorted", route_kernel=cfg.moe_route_kernel,
        norm_topk_prob=cfg.norm_topk_prob, score="sigmoid", select_bias=True,
        routed_scale=cfg.routed_scaling_factor, experts_held=held,
        latent_dim=cfg.moe_latent_size,
        shared_expert=Relu2MLP(cfg, cfg.hidden_size, cfg.moe_shared_expert_intermediate_size),
        param_dtype=cfg.param_dtype, name=name)


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, decode: bool = False, reset=None, valid=None, used=None):
        cfg = self.config
        h = RMSNorm(cfg, name="norm")(x)
        if self.kind == "M":
            out = Mamba2Mixer(cfg, name="mixer")(h, decode, reset, valid)
        elif self.kind == "*":
            out = NemotronHAttention(cfg, name="mixer")(h, decode)
        elif self.kind == "E":
            out = _expert_layer(cfg, "mixer")(h, used_token=used)[0]
        elif self.kind == "-":
            out = Relu2MLP(cfg, cfg.hidden_size, cfg.intermediate_size, name="mixer")(h)
        else:
            raise ValueError(f"hybrid_override_pattern letter {self.kind!r}: "
                             f"one of M, *, E, - is a layer")
        return x + out.astype(x.dtype)


from deepspeed_tpu.models.common import init_cache  # noqa: E402,F401  (re-export)


class NemotronHForCausalLM(nn.Module):
    """Returns logits [B, L, V]. ``decode=True`` runs against the flax
    ``cache`` collection (``mutable=["cache"]``)."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True, decode: bool = False):
        cfg = self.config
        bsz, l = input_ids.shape
        wte = _unboxed(self.param("embed_tokens",
                                  nn.with_logical_partitioning(_init(), ("vocab", "embed")),
                                  (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype))
        from deepspeed_tpu.models.common import embed_lookup
        x = embed_lookup(wte, input_ids, None, decode).astype(cfg.dtype)
        reset = valid = used = None
        if decode:
            # where each sequence writes, and how many of its ``l`` tokens
            # are real: scalars in lockstep ``generate`` (all real, the cache
            # starts zeroed), [slots] vectors in the serving cache, which the
            # serving programs fill from their operands
            index = self.variable("cache", "position_index", lambda: jnp.zeros([], jnp.int32))
            length = self.variable("cache", "chunk_length", lambda: jnp.zeros([], jnp.int32))
            if index.value.ndim:
                valid = length.value
                reset = (index.value == 0) & (valid > 0)
                used = (jnp.arange(l)[None, :] < valid[:, None]).reshape(-1)
            index.value = index.value + l
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            x = NemotronHBlock(cfg, kind, name=f"layers_{i}")(x, decode, reset, valid, used)
        x = RMSNorm(cfg, name="norm_f")(x)
        return _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
