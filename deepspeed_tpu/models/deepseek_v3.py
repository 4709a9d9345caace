"""DeepSeek-V3 family: pre-norm decoder blocks of **multi-head latent
attention** (MLA) and SwiGLU feed-forward layers, the first
``first_k_dense_replace`` dense, the rest sparse (``moe/sharded_moe.py``'s
``MOELayer``: a sigmoid router with a selection bias over all the experts,
the ``k`` chosen weights normalised and scaled, SwiGLU experts, one shared
expert every token passes through); an untied head follows a final RMSNorm.
(JoyAI-LLM-Flash 48B-A2.7B: 40 layers, hidden 2048, 32 heads, one dense layer
of 7168, 256 experts of 768, top-8.)

The attention, for ``x`` a token's normed hidden state and ``h`` a head:

* query: ``c_q = RMSNorm(x W_qa)``; ``[q_nope_h ; q_rope_h] = c_q W_qb``;
  ``q_rope_h`` rotated;
* latent: ``[c ; k_r] = x W_kva``; ``c_kv = RMSNorm(c)``; ``k_r`` rotated,
  one for all heads. **The decode cache holds ``[c_kv ; RoPE(k_r)]`` and
  nothing else** (``models/common.py`` ``LatentCache``): ``kv_lora_rank +
  qk_rope_head_dim`` values a position, once, for all the heads;
* expanded: ``[k_nope_h ; v_h] = c_kv W_kvb``; ``s = (q_nope_h . k_nope_h +
  q_rope_h . k_r) / sqrt(d_nope + d_rope)``; causal softmax; ``W_o`` over the
  heads' ``softmax(s) v_h``;
* absorbed, the same numbers: ``q~_h = q_nope_h W_uk,h^T``; ``s = (q~_h .
  c_kv + q_rope_h . k_r) / sqrt(..)``; ``o_h = (softmax(s) c_kv) W_uv,h``,
  ``W_uk,h`` and ``W_uv,h`` the two halves of head ``h``'s slice of ``W_kvb``.

Which form runs is decided by the shapes, in one module
(:class:`LatentAttention`): **one** new token a sequence against a cache
(a decode tick) is absorbed, and reads the pool as it lies: expanding 16k
cached positions to 32 heads every tick would cost seventeen times the
tick's bytes. Anything longer (a prefill chunk, a whole sequence) is
expanded, a sequence and a block of keys at a time (:func:`expanded_walk`):
the latent of one block is expanded inside the walk, no ``[slots, heads,
chunk, positions]`` scores and no expanded pool ever exist, and a slot's
walk ends at its own live length (a parked slot's is empty).

The residual stream is float32 between the layers whatever they compute in.

RoPE rotates the pairs ``(2i, 2i+1)`` (``rope_interleave``) by ``theta``;
``rope_scaling`` other than none, and group-limited routing (``n_group`` >
1), are not built. Neither is the multi-token-prediction module of the
published checkpoints (``num_nextn_predict_layers``), which never enters
the language model's logits.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.common import (LatentCache, config_from, dense_init as _init,
                                         embed_lookup, rms_norm)
from deepspeed_tpu.models.llama import ExpertKernel


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    rms_norm_eps: float = 1e-6
    # latent attention
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    max_position_embeddings: int = 131072
    # positions the decode cache holds per sequence; None = the context (a
    # server reserves far less for each slot)
    decode_cache_len: Optional[int] = None
    # key positions one step of the expanded walk expands and attends: the
    # chip's choice at chunks of 512 (51% of the walk's roofline; 256 reads
    # 43%, 1,024 30%: PERF.md section 6, PR 32)
    attention_key_block: int = 512
    # feed-forward: dense SwiGLU in the first layers, experts after
    first_k_dense_replace: int = 1
    intermediate_size: int = 7168
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the experts this device holds of ``n_routed_experts``;
    # the router keeps every output (``MOELayer.experts_held``)
    experts_held: Optional[Tuple[int, int]] = None
    moe_route_kernel: str = "auto"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def latent_width(self) -> int:
        """Values the cache holds a position a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


DEEPSEEK_V3_CONFIGS = {
    # JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash): the published sizes
    "joyai-llm-flash": dict(),
    # both kinds of layer at sizes the CPU runs in a second; the key block
    # ends ragged against the test prompts
    "deepseek-v3-test": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, max_position_embeddings=128, attention_key_block=16,
        intermediate_size=96, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32),
}


def get_deepseek_v3_config(name: str, **overrides) -> DeepseekV3Config:
    return config_from(DEEPSEEK_V3_CONFIGS, DeepseekV3Config, name, **overrides)


def _unboxed(p):
    return p.value if isinstance(p, nn.meta.AxisMetadata) else p


class RMSNorm(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        w = _unboxed(self.param("weight", nn.with_logical_partitioning(nn.initializers.ones,
                                                                        (None,)),
                                (x.shape[-1],), cfg.param_dtype))
        return rms_norm(x, w, cfg.rms_norm_eps, cfg.dtype)


def _dense(cfg, features, names, name):
    return nn.Dense(features=features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_logical_partitioning(_init(), names), name=name)


def rotate_interleaved(x, positions, theta: float):
    """RoPE over the last axis of ``x`` [..., l, (heads,) d] at ``positions``
    [..., l], the rotated pairs being neighbours ``(2i, 2i+1)``
    (``rope_interleave``): pair ``i`` turns by ``position * theta^(-2i/d)``.
    ``x`` has the positions on its second axis."""
    d = x.shape[-1]
    # the frequencies in float64 on the host: at position 16k a float32 angle
    # resolves 1e-3 rad, and a last-place error of the frequency is as much again
    inv_freq = jnp.asarray(theta ** (-np.arange(0, d, 2) / d), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq       # [b, l, d/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def walk_blocks(start, fed, block: int, positions: int):
    """Key blocks :func:`expanded_walk` reads of each sequence's pool [b]:
    up to the position of its last real query, none where ``fed`` is 0."""
    n_blocks = jnp.where(fed > 0, -(-(start + fed) // block), 0)
    return jnp.minimum(n_blocks, positions // block).astype(jnp.int32)


def expanded_walk(q_nope, q_rope, pool, w_kvb, start, fed, block: int):
    """Expanded latent attention of ``l`` queries a sequence against that
    sequence's pool, a sequence and ``block`` key positions at a time.

    ``q_nope`` [b, l, H, dn], ``q_rope`` [b, l, H, dr] (rotated), ``pool``
    [b, rank + dr, positions] (positions minor-most, as the serving cache
    stores it; ``positions`` a multiple of ``block``), ``w_kvb`` [rank, H,
    dn + dv]. Query ``i`` of sequence ``s`` stands at position ``start[s] +
    i`` and reads the keys at or before it. ``fed`` [b] says how many of a
    sequence's queries are real: its walk covers ``start + fed`` positions
    and no more, none at all where ``fed`` is 0 (a parked slot, whose result
    is zeros). With ``start`` None every sequence starts at 0 with all its
    queries real, and the loops' lengths are static (differentiable).

    One block's latent is expanded to the heads' keys and values inside the
    step that attends it, with a running softmax over the blocks: the scores
    in flight are [H, l, block] and nothing the size of the pool is made.
    Returns [b, l, H, dv] in ``q_nope``'s dtype."""
    b, l, heads, dn = q_nope.shape
    rank = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - dn
    width, positions = pool.shape[1:]
    scale = (dn + q_rope.shape[-1]) ** -0.5
    dtype = q_nope.dtype
    w_kvb = w_kvb.astype(dtype)

    def one(s, qn, qr, first, n_blocks):
        q_pos = first + jnp.arange(l)

        def step(j, carry):
            m, total, acc = carry
            with jax.named_scope("mla_expand"):
                piece = jax.lax.dynamic_slice(pool, (s, 0, j * block), (1, width, block))[0]
                # keys and values come out positions minor-most, as the pool
                # lies: asking for them position-major has the compiler turn
                # the whole pool over once a layer to suit this matmul
                kv = jnp.einsum("chd,ck->hdk", w_kvb, piece[:rank].astype(dtype))
            with jax.named_scope("mla_attend_prefill"):
                scores = (jnp.einsum("qhd,hdk->hqk", qn, kv[:, :dn],
                                     preferred_element_type=jnp.float32)
                          + jnp.einsum("qhd,dk->hqk", qr, piece[rank:].astype(dtype),
                                       preferred_element_type=jnp.float32)) * scale
                k_pos = j * block + jnp.arange(block)
                scores = jnp.where(k_pos[None, None, :] <= q_pos[None, :, None],
                                   scores, -jnp.inf)
                m_new = jnp.maximum(m, scores.max(axis=-1))
                p = jnp.exp(scores - m_new[..., None])
                shrink = jnp.exp(m - m_new)
                acc = acc * shrink[..., None] + jnp.einsum(
                    "hqk,hdk->hqd", p.astype(dtype), kv[:, dn:],
                    preferred_element_type=jnp.float32)
                return m_new, total * shrink + p.sum(axis=-1), acc

        # the running maximum starts finite: a block with no key a query may
        # read (its scores all -inf) then adds exactly nothing
        init = (jnp.full((heads, l), -1e30, jnp.float32), jnp.zeros((heads, l), jnp.float32),
                jnp.zeros((heads, l, dv), jnp.float32))
        _, total, acc = jax.lax.fori_loop(0, n_blocks, step, init)
        out = acc / jnp.maximum(total, jnp.finfo(jnp.float32).tiny)[..., None]
        return jnp.moveaxis(out, 0, 1).astype(dtype)                    # [l, H, dv]

    if start is None:
        # every query real, from position 0: static trip counts, which scan
        first_of, blocks_of = (lambda s: 0), (lambda s: -(-l // block))
    else:
        n_blocks = walk_blocks(start, fed, block, positions)
        first_of, blocks_of = (lambda s: start[s]), (lambda s: n_blocks[s])

    def sequence(s, out):
        qn = jax.lax.dynamic_index_in_dim(q_nope, s, 0, keepdims=False)
        qr = jax.lax.dynamic_index_in_dim(q_rope, s, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            out, one(s, qn, qr, first_of(s), blocks_of(s)), s, 0)

    return jax.lax.fori_loop(0, b, sequence, jnp.zeros((b, l, heads, dv), dtype))


def absorbed_step(q_nope, q_rope, pool, w_kvb, lengths):
    """Absorbed latent attention of ONE query a sequence over its pool:
    ``q_nope`` [b, H, dn], ``q_rope`` [b, H, dr] (rotated), ``pool`` [b, rank +
    dr, positions] read as it lies, ``lengths`` [b] the positions that hold a
    token (the query's own included; 0: a parked slot, which reads nothing
    that counts). The query goes into the latent space (``W_uk``), scores and
    the weighted sum are taken against the latent itself, and the result
    comes out through ``W_uv``: no key or value of any head is ever made.
    Returns [b, H, dv].

    On a TPU the middle is one kernel (``ops/pallas/latent_decode.py``) that
    reads each live block of the pool once. Elsewhere two matmuls over the
    whole pool: the same numbers, and what the kernel is tested against."""
    dn = q_nope.shape[-1]
    rank = w_kvb.shape[0]
    dtype = q_nope.dtype
    w_kvb = w_kvb.astype(dtype)
    scale = (dn + q_rope.shape[-1]) ** -0.5
    from deepspeed_tpu.ops.pallas import backend
    with jax.named_scope("mla_attend_decode"):
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_kvb[..., :dn])
        if backend.on_tpu():
            from deepspeed_tpu.ops.pallas.latent_decode import latent_decode
            mixed = latent_decode(q_lat, q_rope, pool, lengths, scale=scale)
        else:
            mixed = _mix_whole_pool(q_lat, q_rope, pool, lengths, scale)
        return jnp.einsum("bhc,chd->bhd", mixed.astype(dtype), w_kvb[..., dn:])


def absorbed_positions_read(lengths, positions: int):
    """Positions of the pools :func:`absorbed_step` reads, all sequences: the
    kernel's live blocks, or every position where XLA's two matmuls run."""
    from deepspeed_tpu.ops.pallas import backend
    if not backend.on_tpu():
        return jnp.int32(lengths.shape[0] * positions)
    from deepspeed_tpu.ops.pallas.latent_decode import blocks_read
    blocks, block = blocks_read(lengths, positions)
    return blocks.sum() * block


def _mix_whole_pool(q_lat, q_rope, pool, lengths, scale):
    """``latent_decode`` by XLA, in float32: [b, H, rank]."""
    rank = q_lat.shape[-1]
    pool = pool.astype(jnp.float32)
    # one operand for the pool's one matmul: [q~ ; q_rope] against [c_kv ; k_r]
    q_all = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    scores = jnp.einsum("bhw,bwp->bhp", q_all, pool, precision="highest") * scale
    live = jnp.arange(pool.shape[-1])[None, :] < lengths[:, None]
    scores = jnp.where(live[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jnp.where(live[:, None, :], jax.nn.softmax(scores, axis=-1), 0.0)
    return jnp.einsum("bhp,bwp->bhw", probs, pool, precision="highest")[..., :rank]


class LatentAttention(nn.Module):
    """Multi-head latent attention; see the module's docstring for the
    equations and for which of the two forms runs."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x, decode: bool = False, fed=None):
        cfg = self.config
        b, l, _ = x.shape
        heads, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        rank = cfg.kv_lora_rank

        with jax.named_scope("mla_q"):
            c_q = RMSNorm(cfg, name="q_a_layernorm")(
                _dense(cfg, cfg.q_lora_rank, ("embed", None), "q_a_proj")(x))
            q = nn.DenseGeneral(features=(heads, dn + dr), axis=-1, use_bias=False,
                                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                                kernel_init=nn.with_logical_partitioning(
                                    _init(), (None, "heads", "kv")), name="q_b_proj")(c_q)
        with jax.named_scope("mla_latent"):
            joint = _dense(cfg, rank + dr, ("embed", None), "kv_a_proj_with_mqa")(x)
            c_kv = RMSNorm(cfg, name="kv_a_layernorm")(joint[..., :rank])
        w_kvb = _unboxed(self.param(
            "kv_b_proj", nn.with_logical_partitioning(_init(), (None, "heads", "kv")),
            (rank, heads, dn + dv), cfg.param_dtype))

        start = None
        if decode:
            cache = LatentCache(self, b, cfg.decode_cache_len or cfg.max_position_embeddings,
                                cfg.latent_width, c_kv.dtype)
            first = cache.index.value
            positions = (first[:, None] if cache.per_slot else first) + jnp.arange(l)[None, :]
            positions = jnp.broadcast_to(positions, (b, l))
        else:
            positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        q_nope = q[..., :dn]
        q_rope = rotate_interleaved(q[..., dn:], positions, cfg.rope_theta)
        latent = jnp.concatenate(
            [c_kv, rotate_interleaved(joint[..., rank:], positions, cfg.rope_theta)], axis=-1)

        if decode:
            pool, start = cache.append(latent)
            fed = jnp.full((b,), l, jnp.int32) if fed is None else fed
        if decode and l == 1:
            lengths = jnp.where(fed > 0, start + 1, 0)
            out = absorbed_step(q_nope[:, 0], q_rope[:, 0], pool, w_kvb, lengths)[:, None]
            read = absorbed_positions_read(lengths, pool.shape[-1])
        else:
            block = cfg.attention_key_block
            if not decode:
                # the sequence itself as a pool, padded out to whole blocks
                block = min(block, l)
                pool = jnp.pad(jnp.swapaxes(latent, 1, 2),
                               [(0, 0), (0, 0), (0, -l % block)])
            elif pool.shape[-1] % block:
                block = pool.shape[-1]
            out = expanded_walk(q_nope, q_rope, pool, w_kvb, start, fed, block)
            if decode:
                read = walk_blocks(start, fed, block, pool.shape[-1]).sum() * block
        if decode:
            # for the host, beside a serving tick's tokens: positions of the
            # pool the loops above were bounded to, positions that hold a
            # token, bytes written
            live = jnp.where(fed > 0, jnp.minimum(start + fed, pool.shape[-1]), 0).sum()
            written = fed.sum() * (cfg.latent_width * jnp.dtype(latent.dtype).itemsize)
            self.variable("cache", "latent_reads", jnp.zeros, (3,), jnp.int32).value = (
                jnp.stack([read, live, written]).astype(jnp.int32))
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=nn.with_logical_partitioning(
                                   _init(), ("heads", "kv", "embed")), name="o_proj")(out)


class SwiGLU(nn.Module):
    """``W_down (silu(W_gate x) * W_up x)``. ``num_experts`` > 0 makes it a
    bank that takes rows sorted by expert with their ``group_sizes`` (the
    drop-free sorted route), as ``LlamaMLP``'s bank does."""

    config: DeepseekV3Config
    width: int
    num_experts: int = 0

    @nn.compact
    def __call__(self, x, deterministic: bool = True, group_sizes=None, impl: str = "xla"):
        cfg = self.config
        if not self.num_experts:
            gate = _dense(cfg, self.width, ("embed", "mlp"), "gate_proj")(x)
            up = _dense(cfg, self.width, ("embed", "mlp"), "up_proj")(x)
            return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj")(
                jax.nn.silu(gate) * up)
        if group_sizes is None:
            raise ValueError("a SwiGLU bank takes rows grouped by expert (group_sizes)")
        from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

        def kernel(shape, names, name):
            return ExpertKernel((self.num_experts,) + shape, ("expert",) + names,
                                cfg.param_dtype, name=name)().astype(cfg.dtype)

        w_gate = kernel((cfg.hidden_size, self.width), ("embed", "mlp"), "gate_proj")
        w_up = kernel((cfg.hidden_size, self.width), ("embed", "mlp"), "up_proj")
        w_down = kernel((self.width, cfg.hidden_size), ("mlp", "embed"), "down_proj")
        x = x.astype(cfg.dtype)
        dot = lambda t, w: grouped_matmul(t, w, group_sizes, impl=impl)  # noqa: E731
        return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def _expert_layer(cfg: DeepseekV3Config, name: str):
    """``moe/``'s layer as this family configures it."""
    from deepspeed_tpu.moe.sharded_moe import MOELayer
    held = cfg.experts_held or (0, cfg.n_routed_experts)
    shared = (SwiGLU(cfg, cfg.moe_intermediate_size * cfg.n_shared_experts)
              if cfg.n_shared_experts else None)
    return MOELayer(
        expert=SwiGLU(cfg, cfg.moe_intermediate_size, num_experts=held[1]),
        model_dim=cfg.hidden_size, num_experts=cfg.n_routed_experts, k=cfg.num_experts_per_tok,
        drop_tokens=False, route="sorted", route_kernel=cfg.moe_route_kernel,
        norm_topk_prob=cfg.norm_topk_prob, score="sigmoid", select_bias=True,
        routed_scale=cfg.routed_scaling_factor, experts_held=held, shared_expert=shared,
        param_dtype=cfg.param_dtype, name=name)


class DeepseekV3Block(nn.Module):
    config: DeepseekV3Config
    sparse: bool

    @nn.compact
    def __call__(self, x, decode: bool = False, fed=None, used=None):
        cfg = self.config
        x = x + LatentAttention(cfg, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(x), decode, fed).astype(x.dtype)
        h = RMSNorm(cfg, name="post_attention_layernorm")(x)
        if self.sparse:
            out = _expert_layer(cfg, "mlp")(h, used_token=used)[0]
        else:
            out = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + out.astype(x.dtype)


class DeepseekV3ForCausalLM(nn.Module):
    """Returns logits [B, L, V]. ``decode=True`` runs against the flax
    ``cache`` collection (``mutable=["cache"]``)."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True, decode: bool = False):
        cfg = self.config
        if cfg.n_group != 1 or cfg.topk_group != 1:
            raise NotImplementedError("group-limited routing (n_group > 1) is not built")
        bsz, l = input_ids.shape
        wte = _unboxed(self.param("embed_tokens",
                                  nn.with_logical_partitioning(_init(), ("vocab", "embed")),
                                  (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype))
        # the residual stream is carried in float32 whatever the layers compute
        # in: each block's output is rounded once, to its own size, and not the
        # running sum to the stream's at every add. Ten layers of sigmoid
        # top-k routing turn that rounding into different experts (PERF.md
        # section 6, PR 32)
        x = embed_lookup(wte, input_ids, None, decode).astype(jnp.float32)
        fed = used = None
        if decode:
            # where each sequence writes, and how many of its ``l`` tokens
            # are real: scalars in lockstep ``generate`` (all real), [slots]
            # vectors in the serving cache, which the serving programs fill
            # from their operands. Padding and parked slots route to no
            # expert and bound no walk
            index = self.variable("cache", "position_index", lambda: jnp.zeros([], jnp.int32))
            length = self.variable("cache", "chunk_length", lambda: jnp.zeros([], jnp.int32))
            if index.value.ndim:
                fed = length.value
                used = (jnp.arange(l)[None, :] < fed[:, None]).reshape(-1)
            index.value = index.value + l
        for i in range(cfg.num_hidden_layers):
            x = DeepseekV3Block(cfg, i >= cfg.first_k_dense_replace, name=f"layers_{i}")(
                x, decode, fed, used)
        x = RMSNorm(cfg, name="norm")(x)
        return _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
