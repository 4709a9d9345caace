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
expanded, a sequence and a block of keys at a time: the latent of one block
is expanded inside the walk, no ``[slots, heads, chunk, positions]`` scores
and no expanded pool ever exist, and a slot's walk ends at its own live length
(a parked slot's is empty). The walk is XLA's loops (:func:`expanded_walk`:
every step's float32 scores pass through HBM) or, for a chunk of a full layer
over a cache on a TPU whose shapes the kernel takes (a chunk of a multiple of
16, whole groups of eight heads, whole key blocks), one kernel a fed slot that
keeps them in VMEM (:func:`kernel_walk`, ``ops/pallas/latent_walk.py``); which
of the two follows from the platform, the layer's kind and the shapes, never
from an option:

================  =============================  ================================
layer             chunk over a cache, on a TPU   anywhere else (``decode=False``,
                                                 off the chip, shapes refused)
================  =============================  ================================
plain             the kernel, the causal mask    :func:`expanded_walk`
                  read off the positions inside
                  it (``mla_prefill_walk``)
indexed           the kernel under the slot's    :func:`expanded_walk` under
                  selection, handed as a mask    ``_chosen_chunk``'s ``allow``
                  (``dsa_prefill_walk``)
window            :func:`expanded_walk` under    the same
                  :func:`ring_mask` (a ring is
                  2 MB a slot)
================  =============================  ================================

The residual stream is float32 between the layers whatever they compute in.

**Layers of more than one kind** (``layer_types``; dots3-note-prev: 46 layers,
hidden 5120, 13 full and 33 window layers). Each kind has its own head count,
ranks, key width and theta (:class:`AttentionKind`); the equations above hold
for both, with two additions a configuration may turn on: the normed latents
rescaled (``c_q`` by ``sqrt(hidden / q_lora_rank)``, ``c_kv`` by ``sqrt(hidden
/ kv_lora_rank)``: ``mla_lora_rescale``), and a gate a head, ``o_h <-
sigmoid(x W_g)_h o_h``, before ``W_o`` (``attention_gate`` ``headwise``).

* a **window** layer (``sliding_attention``) attends a token's own position
  and the ``sliding_window_size - 1`` before it. Its decode cache is a RING
  (``models/common.py`` ``RING_LEAVES``): ``window_ring`` positions, the window
  and the longest chunk a call writes, token ``p`` at ``p mod ring``. What a
  ring position holds is read off the query's own position (:func:`ring_mask`),
  so a slot that joins at 0 sees nothing of the ring's last tenant, and
  nothing is ever zeroed. One token a sequence is absorbed over the whole ring
  (:func:`window_step`, XLA: a ring is 2 MB a slot); a chunk walks it expanded.
* an **indexed** layer (``full_attention`` with ``index_topk`` > 0) attends,
  of the positions at or before a token, the ``index_topk`` with the largest
  index score ``I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))`` (all of them
  while there are fewer; equal scores: the lower position), ``qI`` from the
  query latent, ``kI(s)`` one LayerNormed, partly rotated key a position in
  a pool of its own beside the latent's (``INDEX_KEY_LEAVES``). The chosen
  set is found without a sort: the ``index_topk``-th largest score of a row
  by bisection on the scores' bit patterns (:func:`kth_largest`: 32 counts),
  then a mask (:func:`chosen_of`); on the chip one kernel that counts a tile
  of rows in VMEM, as far as its scores were written
  (``ops/pallas/sparse_select.py``). A decode tick scores the slot's live keys
  (``ops/pallas/sparse_index.py``) and runs the absorbed kernel over the live
  latent blocks with the unchosen columns masked; a chunk scores one slot's
  keys for all its queries, and the expanded walk masks each block by them
  (the table above: on the chip the kernel a plain layer's chunk also walks
  in, handed the selection as its mask; elsewhere :func:`expanded_walk` under
  ``allow``). Both compute exactly the reference's set.

RoPE rotates the pairs ``(2i, 2i+1)`` (``rope_interleave``) by ``theta``.
With ``rope_scaling`` (:class:`YarnScaling`; DeepSeek-V3.2: ``factor`` 40 over
4,096 original positions) the frequencies are YaRN's blend
(``models/llama.py`` ``rope_frequencies``, the one place that makes it),
cosine and sine carry ``m(mscale) / m(mscale_all_dim)`` and every softmax
scale ``m(mscale_all_dim)^2``, ``m(s) = 0.1 s ln(factor) + 1``. The indexer
rotates the first ``d_rope`` of its ``index_head_dim`` values by the same
frequencies, in the layer's pairing or, ``index_rope_interleave`` false
(DeepSeek-V3.2's), half-split: pairs ``(i, i + d_rope / 2)``, the two
rotations side by side in one layer.

**Group-limited routing** (``n_group`` > 1; DeepSeek-V3.2: 8 groups of 32,
``topk_group`` 4): the biased scores in groups of consecutive experts, a
group's score its two largest summed, the best groups kept, the ``k`` chosen
among their experts (``moe/sharded_moe.py`` ``group_limited``). An expert
layer that holds a share then sees a token only when its experts' group is
kept, which the layer counts (``moe_group_rows``).

The multi-token-prediction module of the published checkpoints
(``num_nextn_predict_layers``) is not built: it never enters the language
model's logits.
"""

import contextlib
import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.common import (INDEX_KEY_LEAVES, RING_LEAVES, SPARSE_READS, LatentCache,
                                         config_from, dense_init as _init, embed_lookup,
                                         ring_mask, rms_norm,
                                         window_ring_positions)  # noqa: F401  (re-export)
from deepspeed_tpu.models.llama import ExpertKernel, RopeKind, rope_frequencies


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of ``type`` ``yarn``, under the published keys."""
    factor: float
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def m(self, by: float) -> float:
        """``yarn_get_mscale``: 1 where nothing is stretched or ``by`` is 0."""
        return 0.1 * by * float(np.log(self.factor)) + 1.0 if self.factor > 1 else 1.0


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
    # > 1: the ``k`` experts are chosen among those of a token's
    # ``topk_group`` best groups of ``n_group`` (consecutive experts)
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the experts this device holds of ``n_routed_experts``;
    # the router keeps every output (``MOELayer.experts_held``)
    experts_held: Optional[Tuple[int, int]] = None
    moe_route_kernel: str = "auto"
    # layers of more than one kind: a "full_attention" or "sliding_attention"
    # a layer (None: all full). A sliding layer reads the ``swa_*`` sizes and
    # attends ``sliding_window_size`` positions, its own included
    layer_types: Optional[Tuple[str, ...]] = None
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    # positions of a sliding layer's decode cache, a ring (None: as many as
    # a full layer's pool, which never wraps): at least the window less one
    # and the longest chunk a call writes (:func:`window_ring_positions`)
    window_ring: Optional[int] = None
    # the indexer of the full layers: 0 = none, every position attended
    index_topk: int = 0
    index_n_heads: int = 64
    index_head_dim: int = 128
    # the indexer's rotated pairs: neighbours, as the layer's own, or
    # half-split ``(i, i + d_rope / 2)`` (DeepSeek-V3.2's indexer)
    index_rope_interleave: bool = True
    # None, a :class:`YarnScaling`, or its published dict (``type`` yarn)
    rope_scaling: Optional[YarnScaling] = None
    mla_lora_rescale: bool = False
    # None, or "headwise": one sigmoid gate a head from the layer's input
    attention_gate: Optional[str] = None
    swa_attention_gate: Optional[str] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        scaling = self.rope_scaling
        if isinstance(scaling, dict):
            scaling = dict(scaling)
            kind = scaling.pop("type", scaling.pop("rope_type", "yarn"))
            if kind != "yarn":
                raise NotImplementedError(f"rope_scaling of type {kind!r}: only yarn is built")
            object.__setattr__(self, "rope_scaling", YarnScaling(**scaling))

    @property
    def latent_width(self) -> int:
        """Values a full layer's cache holds a position."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def kind_of(self, layer: int) -> "AttentionKind":
        """The sizes of layer ``layer``'s attention."""
        kind = self.layer_types[layer] if self.layer_types else "full_attention"
        if kind == "sliding_attention":
            return AttentionKind(
                self.swa_num_attention_heads, self.swa_q_lora_rank, self.swa_kv_lora_rank,
                self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta, self.swa_attention_gate, window=self.sliding_window_size,
                yarn=self.rope_scaling)
        if kind != "full_attention":
            raise NotImplementedError(f"layer_types[{layer}] = {kind!r}: not built")
        return AttentionKind(self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
                             self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
                             self.rope_theta, self.attention_gate, top_k=self.index_topk,
                             yarn=self.rope_scaling)


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """One kind of latent-attention layer: its sizes, and what it attends."""
    heads: int
    q_rank: int
    rank: int
    d_nope: int
    d_rope: int
    d_value: int
    theta: float
    gate: Optional[str] = None
    window: int = 0         # > 0: this position and the ``window - 1`` before it
    top_k: int = 0          # > 0: the ``top_k`` positions the indexer scores highest
    yarn: Optional[YarnScaling] = None

    def frequencies(self):
        """``(inverse frequencies [d_rope / 2] float32, the factor on cosine
        and sine)``, made in float64 on the host: at position 16k a float32
        angle resolves 1e-3 rad, and a last-place error of the frequency is as
        much again. Plain or YaRN's, they are ``models/llama.py``'s."""
        y = self.yarn
        rope = RopeKind(theta=self.theta) if y is None else RopeKind(
            theta=self.theta, yarn_factor=y.factor,
            original_positions=y.original_max_position_embeddings, beta_fast=y.beta_fast,
            beta_slow=y.beta_slow, attention_factor=y.m(y.mscale) / y.m(y.mscale_all_dim))
        return rope_frequencies(rope, self.d_rope)

    @property
    def softmax_scale(self) -> float:
        """``1 / sqrt(d_nope + d_rope)``, times YaRN's ``m(mscale_all_dim)^2``."""
        scale = (self.d_nope + self.d_rope) ** -0.5
        return scale if self.yarn is None else scale * self.yarn.m(self.yarn.mscale_all_dim) ** 2


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
    # dots3-note-prev (dots-studio/dots3-note-prev): the published sizes
    "dots3-note-prev": dict(
        vocab_size=152064, hidden_size=5120, num_hidden_layers=46, rms_norm_eps=1e-5,
        num_attention_heads=128, q_lora_rank=1024, rope_theta=80000000.0,
        max_position_embeddings=524288, intermediate_size=13824, moe_intermediate_size=1536,
        routed_scaling_factor=1.0,
        layer_types=("full_attention",) + ("full_attention",) + 11 * (
            3 * ("sliding_attention",) + ("full_attention",)),
        index_topk=2048, mla_lora_rescale=True, attention_gate="headwise",
        swa_attention_gate="headwise"),
    # a dense indexed layer, an indexed expert layer and two window layers,
    # at sizes where the selection and the window both bind inside 128
    # positions and the ring wraps
    "dots3-note-test": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=4, rms_norm_eps=1e-5,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, max_position_embeddings=128, attention_key_block=16,
        intermediate_size=96, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, routed_scaling_factor=1.0,
        layer_types=("full_attention", "full_attention", "sliding_attention",
                     "sliding_attention"),
        swa_num_attention_heads=2, swa_q_lora_rank=40, swa_kv_lora_rank=48,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4,
        sliding_window_size=17, window_ring=32, index_topk=24, index_n_heads=4,
        index_head_dim=16, mla_lora_rescale=True, attention_gate="headwise",
        swa_attention_gate="headwise"),
    # DeepSeek-V3.2 (deepseek-ai/DeepSeek-V3.2): the published sizes
    "deepseek-v3.2": dict(
        hidden_size=7168, num_hidden_layers=61, num_attention_heads=128, rope_theta=10000.0,
        max_position_embeddings=163840, first_k_dense_replace=3, intermediate_size=18432,
        moe_intermediate_size=2048, n_group=8, topk_group=4, index_topk=2048,
        index_rope_interleave=False,
        rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)),
    # a dense and three expert layers, every one indexed, at sizes where the
    # selection binds inside 128 positions, YaRN's ramp lies inside the four
    # rotated pairs and the 16 experts stand in 4 groups of which 2 are kept
    "deepseek-v3.2-test": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10000.0, max_position_embeddings=128, attention_key_block=16,
        intermediate_size=96, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, n_group=4, topk_group=2, index_topk=24, index_n_heads=4,
        index_head_dim=16, index_rope_interleave=False,
        rope_scaling=YarnScaling(factor=8.0, original_max_position_embeddings=16,
                                 beta_fast=2.0, beta_slow=0.25, mscale=1.0, mscale_all_dim=1.0)),
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


def rope_turn(x, positions, inv_freq, factor: float = 1.0, interleaved: bool = True):
    """RoPE over the last axis of ``x`` [..., l, (heads,) d] at ``positions``
    [..., l]: pair ``i`` turns by ``position * inv_freq[i]`` (``inv_freq``
    [d / 2], host float32), cosine and sine times ``factor``. The pairs are
    neighbours ``(2i, 2i+1)`` (``rope_interleave``) or, ``interleaved`` false,
    half-split ``(i, i + d / 2)``. ``x`` has the positions on its second axis."""
    d = x.shape[-1]
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    if not interleaved:
        first, second = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([first * cos - second * sin, second * cos + first * sin],
                               axis=-1).astype(x.dtype)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotate_interleaved(x, positions, theta: float):
    """:func:`rope_turn` of neighbouring pairs by ``theta`` alone: pair ``i``
    turns by ``position * theta^(-2i/d)``, the frequencies made in float64 on
    the host."""
    d = x.shape[-1]
    return rope_turn(x, positions, theta ** (-np.arange(0, d, 2) / d))


def walk_blocks(start, fed, block: int, positions: int):
    """Key blocks :func:`expanded_walk` reads of each sequence's pool [b]:
    up to the position of its last real query, none where ``fed`` is 0."""
    n_blocks = jnp.where(fed > 0, -(-(start + fed) // block), 0)
    return jnp.minimum(n_blocks, positions // block).astype(jnp.int32)


def expanded_walk(q_nope, q_rope, pool, w_kvb, start, fed, block: int, allow=None,
                  scale: Optional[float] = None):
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
    Returns [b, l, H, dv] in ``q_nope``'s dtype. ``scale`` multiplies the
    scores (None: ``1 / sqrt(dn + dr)``; ``AttentionKind.softmax_scale``).

    ``allow`` replaces the causal mask: ``allow(s, q_pos)`` is called once a
    sequence and returns ``(state, mask)``, and ``mask(j, k_at, state)`` once a
    block, ``k_at`` [block] the block's places in the pool, giving ``(which
    [l, block] bool, state)``: what each query may read there (a window layer's
    ring, an indexed layer's chosen positions)."""
    b, l, heads, dn = q_nope.shape
    rank = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - dn
    width, positions = pool.shape[1:]
    scale = (dn + q_rope.shape[-1]) ** -0.5 if scale is None else scale
    dtype = q_nope.dtype
    w_kvb = w_kvb.astype(dtype)

    def one(s, qn, qr, first, n_blocks):
        q_pos = first + jnp.arange(l)
        state, mask = ((), None) if allow is None else allow(s, q_pos)

        def step(j, carry):
            m, total, acc, state = carry
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
                if mask is None:
                    seen = k_pos[None, :] <= q_pos[:, None]
                else:
                    seen, state = mask(j, k_pos, state)
                scores = jnp.where(seen[None], scores, -jnp.inf)
                m_new = jnp.maximum(m, scores.max(axis=-1))
                p = jnp.exp(scores - m_new[..., None])
                shrink = jnp.exp(m - m_new)
                acc = acc * shrink[..., None] + jnp.einsum(
                    "hqk,hdk->hqd", p.astype(dtype), kv[:, dn:],
                    preferred_element_type=jnp.float32)
                return m_new, total * shrink + p.sum(axis=-1), acc, state

        # the running maximum starts finite: a block with no key a query may
        # read (its scores all -inf) then adds exactly nothing
        init = (jnp.full((heads, l), -1e30, jnp.float32), jnp.zeros((heads, l), jnp.float32),
                jnp.zeros((heads, l, dv), jnp.float32), state)
        _, total, acc, _ = jax.lax.fori_loop(0, n_blocks, step, init)
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


def kernel_walk(q_nope, q_rope, pool, w_kvb, start, fed, chosen=None,
                scale: Optional[float] = None):
    """:func:`expanded_walk` on the chip, a fed slot at a time through the
    kernel that keeps a step's scores in VMEM (``ops/pallas/latent_walk.py``;
    shapes it ``takes``): the slot's pool is read in place, blocks past
    ``start + fed`` are neither moved nor run, and a slot that is fed nothing
    reads nothing and gives zeros. A plain layer's queries attend every
    position at or before their own, the mask read off the positions inside
    the kernel; an indexed layer hands ``chosen(s)``, the [l, positions]
    float32 mask of slot ``s`` (> 0: attended; causality included). Same
    operands and result as :func:`expanded_walk`."""
    from deepspeed_tpu.ops.pallas import latent_walk
    b, l, heads, dn = q_nope.shape
    dv = w_kvb.shape[-1] - dn
    positions, dtype = pool.shape[-1], q_nope.dtype
    scale = (dn + q_rope.shape[-1]) ** -0.5 if scale is None else scale
    # heads first, as the kernel blocks them: the weights once, a fed slot's
    # queries as its turn comes (an unfed slot's are never moved)
    w = jnp.swapaxes(w_kvb, 0, 1).astype(dtype)                          # [H, rank, dn + dv]
    of_slot = lambda t, s: jnp.moveaxis(  # noqa: E731  [H, l, d]
        jax.lax.dynamic_index_in_dim(t, s, 0, keepdims=False), 1, 0)

    def attend(s):
        blocks, _ = latent_walk.walk_blocks(start[s] + fed[s], positions)
        operands = of_slot(q_nope, s), of_slot(q_rope, s), w, pool
        if chosen is None:
            out = latent_walk.causal_walk(*operands, start[s], s, blocks, scale=scale)
        else:
            out = latent_walk.selected_walk(*operands, chosen(s), s, blocks, scale=scale)
        return jnp.moveaxis(out, 0, 1)                                   # [l, H, dv]

    def sequence(s, out):
        rows = jax.lax.cond(fed[s] > 0, attend, lambda s: jnp.zeros((l, heads, dv), dtype), s)
        return jax.lax.dynamic_update_index_in_dim(out, rows, s, 0)

    return jax.lax.fori_loop(0, b, sequence, jnp.zeros((b, l, heads, dv), dtype))


def absorbed_step(q_nope, q_rope, pool, w_kvb, lengths, chosen=None,
                  scale: Optional[float] = None):
    """Absorbed latent attention of ONE query a sequence over its pool:
    ``q_nope`` [b, H, dn], ``q_rope`` [b, H, dr] (rotated), ``pool`` [b, rank +
    dr, positions] read as it lies, ``lengths`` [b] the positions that hold a
    token (the query's own included; 0: a parked slot, which reads nothing
    that counts). The query goes into the latent space (``W_uk``), scores and
    the weighted sum are taken against the latent itself, and the result
    comes out through ``W_uv``: no key or value of any head is ever made.
    ``chosen`` [b, positions] bool (an indexed layer): the live positions the
    softmax runs over. Returns [b, H, dv].

    On a TPU the middle is one kernel (``ops/pallas/latent_decode.py``) that
    reads each live block of the pool once. Elsewhere two matmuls over the
    whole pool: the same numbers, and what the kernel is tested against."""
    dn = q_nope.shape[-1]
    rank = w_kvb.shape[0]
    dtype = q_nope.dtype
    w_kvb = w_kvb.astype(dtype)
    scale = (dn + q_rope.shape[-1]) ** -0.5 if scale is None else scale
    from deepspeed_tpu.ops.pallas import backend
    with jax.named_scope("mla_attend_decode" if chosen is None else "dsa_attend_decode"):
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_kvb[..., :dn])
        if backend.on_tpu():
            from deepspeed_tpu.ops.pallas.latent_decode import latent_decode
            mixed = latent_decode(q_lat, q_rope, pool, lengths, scale=scale, chosen=chosen)
        else:
            mixed = _mix_whole_pool(q_lat, q_rope, pool, lengths, scale, chosen)
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


def _mix_whole_pool(q_lat, q_rope, pool, lengths, scale, chosen=None):
    """``latent_decode`` by XLA, in float32: [b, H, rank]."""
    rank = q_lat.shape[-1]
    pool = pool.astype(jnp.float32)
    # one operand for the pool's one matmul: [q~ ; q_rope] against [c_kv ; k_r]
    q_all = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    scores = jnp.einsum("bhw,bwp->bhp", q_all, pool, precision="highest") * scale
    live = jnp.arange(pool.shape[-1])[None, :] < lengths[:, None]
    if chosen is not None:
        live = live & chosen
    scores = jnp.where(live[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jnp.where(live[:, None, :], jax.nn.softmax(scores, axis=-1), 0.0)
    return jnp.einsum("bhp,bwp->bhw", probs, pool, precision="highest")[..., :rank]


def window_step(q_nope, q_rope, ring, w_kvb, pos, live, window: int,
                scale: Optional[float] = None):
    """Absorbed latent attention of ONE query a sequence over its RING
    [b, rank + dr, ring]: the query at ``pos`` [b] (already written) reads
    its window (:func:`ring_mask`); a sequence that is not ``live`` gives
    zeros. XLA, in the compute type with float32 sums: a ring is two
    thousandths of a pool. Returns [b, H, dv]."""
    dn = q_nope.shape[-1]
    rank = w_kvb.shape[0]
    dtype = q_nope.dtype
    w_kvb = w_kvb.astype(dtype)
    scale = (dn + q_rope.shape[-1]) ** -0.5 if scale is None else scale
    with jax.named_scope("swa_attend_decode"):
        q_all = jnp.concatenate(
            [jnp.einsum("bhd,chd->bhc", q_nope, w_kvb[..., :dn]), q_rope], axis=-1)
        held = ring.astype(dtype)
        scores = jnp.einsum("bhw,bwp->bhp", q_all, held,
                            preferred_element_type=jnp.float32) * scale
        seen = jax.vmap(lambda t: ring_mask(t[None], jnp.arange(ring.shape[-1]),
                                            ring.shape[-1], window)[0])(pos)
        seen = seen & live[:, None]
        scores = jnp.where(seen[:, None, :], scores, jnp.finfo(jnp.float32).min)
        probs = jnp.where(seen[:, None, :], jax.nn.softmax(scores, axis=-1), 0.0)
        mixed = jnp.einsum("bhp,bcp->bhc", probs.astype(dtype), held[:, :rank],
                           preferred_element_type=jnp.float32)
        return jnp.einsum("bhc,chd->bhd", mixed.astype(dtype), w_kvb[..., dn:])


def index_scores(q, w, keys):
    """``I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))`` by XLA: ``q`` [..., l, J,
    d], ``w`` [..., l, J] float32, ``keys`` [..., d, n] -> [..., l, n] float32.
    Products in the keys' type with float32 sums, as the kernels make them."""
    products = jnp.einsum("...ljd,...dn->...ljn", q.astype(keys.dtype), keys,
                          preferred_element_type=jnp.float32,
                          precision="highest" if keys.dtype == jnp.float32 else None)
    return jnp.einsum("...ljn,...lj->...ln", jnp.maximum(products, 0.0),
                      w.astype(jnp.float32), precision="highest")


def _ordered(scores):
    """float32 scores as uint32 in the same order (-0 as +0), never 0."""
    scores = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    bits = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(scores, valid, k: int):
    """Of each row of ``scores`` [..., n] float32 where ``valid``: ``(keys,
    bar, quota)``: the scores as ordered uint32 ``keys`` (0 where not
    valid), the ``k``-th largest valid key ``bar`` [...] (0 where fewer than
    ``k`` are valid: every valid key is above it), and how many keys EQUAL to
    the bar belong to the top ``k``, ``quota`` [...]. No sort: the bar is
    built a bit at a time from the top, each bit one count of the row."""
    keys = jnp.where(valid, _ordered(scores), jnp.uint32(0))

    def bit(i, bar):
        raised = bar | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where((keys >= raised[..., None]).sum(axis=-1) >= k, raised, bar)

    bar = jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))
    quota = k - (keys > bar[..., None]).sum(axis=-1)
    return keys, bar, quota.astype(jnp.int32)


def chosen_of(keys, bar, quota, tied_before=None):
    """The top-``k`` set as a mask, from :func:`kth_largest`'s three: every
    key above the bar, and of those equal to it the first ``quota`` by
    position. ``keys`` may be a run of columns of the rows ``bar`` / ``quota``
    were found over: ``tied_before`` [...] is then how many keys equal to the
    bar lie before the run. Returns ``(chosen, tied so far)``."""
    tied = (keys == bar[..., None]) & (keys > 0)
    before = 0 if tied_before is None else tied_before[..., None]
    rank = before + jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
    chosen = (keys > bar[..., None]) | (tied & (rank <= quota[..., None]))
    return chosen, rank[..., -1]


class LatentAttention(nn.Module):
    """Multi-head latent attention of one ``kind``; see the module's docstring
    for the equations, for which of the two forms runs, and for what a window
    layer and an indexed layer attend."""

    config: DeepseekV3Config
    kind: AttentionKind

    def _indexer(self, x, c_q, turn):
        """The indexer's three: ``q`` [b, l, J, d] and the one key a position
        ``k`` [b, l, d], the first ``d_rope`` of their ``d`` values rotated by
        the layer's frequencies (``turn``) in the indexer's own pairing
        (``index_rope_interleave``), and the heads' weights ``w`` [b, l, J]
        float32."""
        cfg, kind = self.config, self.kind
        heads, d = cfg.index_n_heads, cfg.index_head_dim

        def rotated(t):
            return jnp.concatenate([turn(t[..., :kind.d_rope], cfg.index_rope_interleave),
                                    t[..., kind.d_rope:]], -1)

        q = nn.DenseGeneral(features=(heads, d), axis=-1, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            kernel_init=nn.with_logical_partitioning(_init(), (None, None, None)),
                            name="indexer_q_proj")(c_q)
        k = nn.LayerNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="indexer_k_norm")(
            _dense(cfg, d, ("embed", None), "indexer_k_proj")(x))
        w = _dense(cfg, heads, ("embed", None), "indexer_weights_proj")(x)
        return rotated(q), rotated(k), w.astype(jnp.float32) * (heads * d) ** -0.5

    @nn.compact
    def __call__(self, x, decode: bool = False, fed=None):
        cfg, kind = self.config, self.kind
        b, l, _ = x.shape
        heads, dn, dr, dv, rank = kind.heads, kind.d_nope, kind.d_rope, kind.d_value, kind.rank
        width = rank + dr
        a_q = (cfg.hidden_size / kind.q_rank) ** 0.5 if cfg.mla_lora_rescale else 1.0
        a_kv = (cfg.hidden_size / rank) ** 0.5 if cfg.mla_lora_rescale else 1.0

        with jax.named_scope("mla_q"):
            c_q = RMSNorm(cfg, name="q_a_layernorm")(
                _dense(cfg, kind.q_rank, ("embed", None), "q_a_proj")(x))
            if a_q != 1.0:
                c_q = (c_q * a_q).astype(c_q.dtype)
            q = nn.DenseGeneral(features=(heads, dn + dr), axis=-1, use_bias=False,
                                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                                kernel_init=nn.with_logical_partitioning(
                                    _init(), (None, "heads", "kv")), name="q_b_proj")(c_q)
        with jax.named_scope("mla_latent"):
            joint = _dense(cfg, width, ("embed", None), "kv_a_proj_with_mqa")(x)
            c_kv = RMSNorm(cfg, name="kv_a_layernorm")(joint[..., :rank])
            if a_kv != 1.0:
                c_kv = (c_kv * a_kv).astype(c_kv.dtype)
        w_kvb = _unboxed(self.param(
            "kv_b_proj", nn.with_logical_partitioning(_init(), (None, "heads", "kv")),
            (rank, heads, dn + dv), cfg.param_dtype))

        start = None
        extent = cfg.decode_cache_len or cfg.max_position_embeddings
        if decode:
            if kind.window:
                cache = LatentCache(self, b, cfg.window_ring or extent, width, c_kv.dtype,
                                    name=RING_LEAVES[0], ring=True)
            else:
                cache = LatentCache(self, b, extent, width, c_kv.dtype)
            first = cache.index.value
            positions = (first[:, None] if cache.per_slot else first) + jnp.arange(l)[None, :]
            positions = jnp.broadcast_to(positions, (b, l))
        else:
            positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        inv_freq, factor = kind.frequencies()
        scale = kind.softmax_scale

        def turn(t, interleaved=True):
            with jax.named_scope("rope_yarn") if kind.yarn else contextlib.nullcontext():
                return rope_turn(t, positions, inv_freq, factor, interleaved)

        q_nope = q[..., :dn]
        q_rope = turn(q[..., dn:])
        latent = jnp.concatenate([c_kv, turn(joint[..., rank:])], axis=-1)
        if kind.top_k:
            with jax.named_scope("dsa_index"):
                index_q, index_k, index_w = self._indexer(x, c_q, turn)

        keys = None
        if decode:
            fed = jnp.full((b,), l, jnp.int32) if fed is None else fed
            if kind.window and l > cache.positions - (kind.window - 1):
                raise ValueError(
                    f"a call of {l} tokens over a ring of {cache.positions} positions "
                    f"overwrites what its first query's window of {kind.window} still reads: "
                    f"window_ring must be window_ring_positions(window, the longest chunk)")
            if kind.top_k:
                keys, _ = LatentCache(self, b, extent, cfg.index_head_dim, index_k.dtype,
                                      name=INDEX_KEY_LEAVES[0], index=cache.index).append(
                                          index_k, advance=False)
            pool, start = cache.append(latent, live=fed > 0)
        counts = {}
        if decode and l == 1:
            lengths = jnp.where(fed > 0, start + 1, 0)
            if kind.window:
                out = window_step(q_nope[:, 0], q_rope[:, 0], pool, w_kvb, start, fed > 0,
                                  kind.window, scale)[:, None]
                counts = {"swa_ring_positions_read": (fed > 0).sum() * pool.shape[-1],
                          "swa_ring_positions_live": jnp.minimum(lengths, kind.window).sum()}
            else:
                chosen = None
                if kind.top_k:
                    chosen, counts = self._chosen_decode(index_q[:, 0], index_w[:, 0], keys,
                                                         lengths)
                out = absorbed_step(q_nope[:, 0], q_rope[:, 0], pool, w_kvb, lengths,
                                    chosen, scale)[:, None]
                read = absorbed_positions_read(lengths, pool.shape[-1])
        else:
            block = cfg.attention_key_block
            if not decode:
                # the sequence itself as a pool, padded out to whole blocks
                block = min(block, l)
                as_pool = lambda t: jnp.pad(jnp.swapaxes(t, 1, 2),  # noqa: E731
                                            [(0, 0), (0, 0), (0, -l % block)])
                pool = as_pool(latent)
                keys = as_pool(index_k) if kind.top_k else None
            elif pool.shape[-1] % block:
                block = pool.shape[-1]
            in_kernel = self._walks_in_kernel(l, pool, start)
            if in_kernel:
                from deepspeed_tpu.ops.pallas import latent_walk
                block = min(latent_walk.BLOCK, pool.shape[-1])
            allow = None
            if kind.window:
                places, window = pool.shape[-1], kind.window
                allow = lambda s, q_pos: ((), lambda j, k_at, state: (  # noqa: E731
                    ring_mask(q_pos, k_at, places, window), state))
            elif kind.top_k:
                allow = self._chosen_chunk(index_q, index_w, keys, start, block)
            with (jax.named_scope("swa_attend_prefill") if kind.window else
                  jax.named_scope("dsa_attend_prefill") if kind.top_k
                  else contextlib.nullcontext()):
                if in_kernel:
                    out = kernel_walk(q_nope, q_rope, pool, w_kvb, start, fed,
                                      self._chosen_in_kernel(index_q, index_w, keys, start, fed)
                                      if kind.top_k else None, scale)
                else:
                    out = expanded_walk(q_nope, q_rope, pool, w_kvb, start, fed, block, allow,
                                        scale)
            if decode:
                read = walk_blocks(start, fed, block, pool.shape[-1]).sum() * block
                ends = jnp.where(fed > 0, start + fed, 0)
                if kind.window:
                    counts = {"swa_ring_positions_read": read,
                              "swa_ring_positions_live": jnp.minimum(ends, fed + kind.window - 1).sum()}
                elif kind.top_k:
                    from deepspeed_tpu.ops.pallas import sparse_select
                    from deepspeed_tpu.ops.pallas.sparse_index import chunk_blocks
                    blocks, size = chunk_blocks(ends, keys.shape[-1])
                    each = start[:, None] + 1 + jnp.arange(l)[None, :]          # [b, l]
                    real = jnp.arange(l)[None, :] < fed[:, None]
                    counts = {"dsa_index_keys_read": blocks.sum() * size,
                              "dsa_select_positions_read": sparse_select.tile_blocks(
                                  fed, blocks, l).sum() * (sparse_select.row_tile(l) * size)
                              if in_kernel else (fed > 0).sum() * (l * keys.shape[-1]),
                              "dsa_positions_selected": jnp.where(
                                  real, jnp.minimum(each, kind.top_k), 0).sum(),
                              "dsa_positions_live": jnp.where(real, each, 0).sum()}
        if decode:
            # for the host, beside a serving tick's tokens: positions of the
            # pool the loops above were bounded to, positions that hold a
            # token, bytes written
            wide = jnp.dtype(latent.dtype).itemsize
            if not kind.window:
                live = jnp.where(fed > 0, jnp.minimum(start + fed, pool.shape[-1]), 0).sum()
                written = fed.sum() * (width * wide)
                self.variable("cache", "latent_reads", jnp.zeros, (3,), jnp.int32).value = (
                    jnp.stack([read, live, written]).astype(jnp.int32))
            if kind.window or kind.top_k:
                counts["swa_ring_bytes_written" if kind.window else "dsa_latent_bytes_written"] = fed.sum() * width * wide
                if kind.top_k:
                    counts["dsa_index_key_bytes_written"] = fed.sum() * cfg.index_head_dim * wide
                self.variable("cache", "sparse_reads", jnp.zeros, (len(SPARSE_READS),),
                              jnp.int32).value = jnp.stack(
                    [jnp.asarray(counts.get(name, 0), jnp.int32) for name in SPARSE_READS])
        if kind.gate:
            if kind.gate != "headwise":
                raise NotImplementedError(f"attention gate {kind.gate!r}: only headwise is built")
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(_dense(cfg, heads, ("embed", "heads"), "gate_proj")(x))
                out = out * gate[..., None].astype(out.dtype)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=nn.with_logical_partitioning(
                                   _init(), ("heads", "kv", "embed")), name="o_proj")(out)

    def _chosen_decode(self, q, w, keys, lengths):
        """One query a sequence: ``(chosen [b, positions], counts)``, the
        ``top_k`` live positions its index scores put first: bool by XLA, on
        the chip the float32 mask of ``sparse_select.select_top_k``, written
        as far as a tile of sequences has live blocks."""
        from deepspeed_tpu.ops.pallas import backend, sparse_select
        top_k, (b, positions) = self.kind.top_k, (keys.shape[0], keys.shape[-1])
        in_kernel = backend.on_tpu() and sparse_select.takes(b, positions)
        with jax.named_scope("dsa_index"):
            if backend.on_tpu():
                from deepspeed_tpu.ops.pallas.latent_decode import blocks_read
                from deepspeed_tpu.ops.pallas.sparse_index import BLOCK, index_scores_decode
                scores = index_scores_decode(q, w, keys, lengths)
                blocks, block = blocks_read(lengths, positions, BLOCK)
                keys_read = blocks.sum() * block
            else:
                scores = index_scores(q[:, None], w[:, None], keys)[:, 0]
                keys_read = (lengths > 0).sum() * positions
        with jax.named_scope("dsa_select"):
            live = jnp.arange(positions)[None, :] < lengths[:, None]
            if in_kernel:
                tile = sparse_select.row_tile(b)
                blocks = blocks.reshape(-1, tile).max(axis=-1)
                chosen = sparse_select.select_top_k(scores, lengths, blocks, top_k)
                selected_from = blocks.sum() * (tile * block)
            else:
                chosen, _ = chosen_of(*kth_largest(scores, live, top_k))
                selected_from = (lengths > 0).sum() * positions
        self.sow("intermediates", "dsa_chosen", (live & (chosen > 0))[:, None])
        return chosen, {"dsa_index_keys_read": keys_read,
                        "dsa_select_positions_read": selected_from,
                        "dsa_positions_selected": jnp.minimum(lengths, top_k).sum(),
                        "dsa_positions_live": lengths.sum()}

    def _walks_in_kernel(self, l: int, pool, start) -> bool:
        """Whether a chunk of a full layer over a cache's pool walks it in the
        chip's kernel (:func:`kernel_walk`; an indexed layer with the two
        kernels that score and select before it, a slot at a time)."""
        from deepspeed_tpu.ops.pallas import backend, latent_walk, sparse_index, sparse_select
        kind = self.kind
        if not (backend.on_tpu() and start is not None and not kind.window
                and latent_walk.takes(l, kind.heads, pool.shape[-1])):
            return False
        return bool(not kind.top_k or (sparse_index.chunk_tile(l)
                                       and sparse_select.takes(l, pool.shape[-1])))

    def _chosen_in_kernel(self, index_q, index_w, keys, start, fed):
        """The ``chosen`` of :func:`kernel_walk` for a chunk of an indexed
        layer on the chip: a slot's index scores
        (``sparse_index.index_scores_chunk``) and the mask of each query's
        chosen over the slot's live blocks (``sparse_select.select_top_k``: the
        scores are read once). The set is :meth:`_chosen_chunk`'s."""
        from deepspeed_tpu.ops.pallas import sparse_index, sparse_select
        b, l = index_q.shape[:2]
        positions, top_k = keys.shape[-1], self.kind.top_k
        index_q = index_q.reshape(b, l, -1)
        slot_of = lambda t, s: jax.lax.dynamic_index_in_dim(t, s, 0, keepdims=False)  # noqa: E731

        def chosen(s):
            q_pos = start[s] + jnp.arange(l)
            with jax.named_scope("dsa_index"):
                blocks, _ = sparse_index.chunk_blocks(start[s] + fed[s], positions)
                scores = sparse_index.index_scores_chunk(slot_of(index_q, s), slot_of(index_w, s),
                                                         keys, s, blocks)
            with jax.named_scope("dsa_select"):
                # the tiles of rows past the slot's real queries choose nothing
                return sparse_select.select_top_k(
                    scores, q_pos + 1, sparse_select.tile_blocks(fed[s], blocks, l), top_k)

        return chosen

    def _chosen_chunk(self, q, w, keys, start, block: int):
        """The ``allow`` of :func:`expanded_walk` for a chunk of an indexed
        layer, by XLA (off the chip; on it :meth:`_walk_chosen`): once a
        sequence, the index scores of its ``l`` queries over its keys (one
        slot's, never every slot's at once) and each query's bar; once a
        block, the mask of the chosen among the block's positions."""
        top_k, positions = self.kind.top_k, keys.shape[-1]
        b, l = q.shape[:2]
        slot_of = lambda t, s: jax.lax.dynamic_index_in_dim(t, s, 0, keepdims=False)  # noqa: E731

        def allow(s, q_pos):
            with jax.named_scope("dsa_index"):
                scores = index_scores(slot_of(q, s), slot_of(w, s), slot_of(keys, s))
            with jax.named_scope("dsa_select"):
                valid = jnp.arange(positions)[None, :] <= q_pos[:, None]
                ordered, bar, quota = kth_largest(scores, valid, top_k)

            def mask(j, k_at, tied):
                run = jax.lax.dynamic_slice(ordered, (0, j * block), (l, block))
                return chosen_of(run, bar, quota, tied)

            return jnp.zeros((l,), jnp.int32), mask

        if self.is_mutable_collection("intermediates"):
            # for the tests: every query's chosen set over the whole pool
            first = jnp.zeros((b,), jnp.int32) if start is None else start
            valid = (jnp.arange(positions)[None, None, :]
                     <= (first[:, None] + jnp.arange(l)[None, :])[..., None])
            self.sow("intermediates", "dsa_chosen",
                     chosen_of(*kth_largest(index_scores(q, w, keys), valid, top_k))[0])
        return allow


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
        routed_scale=cfg.routed_scaling_factor, n_group=cfg.n_group,
        topk_group=cfg.topk_group, experts_held=held, shared_expert=shared,
        param_dtype=cfg.param_dtype, name=name)


class DeepseekV3Block(nn.Module):
    config: DeepseekV3Config
    sparse: bool
    layer: int = 0

    @nn.compact
    def __call__(self, x, decode: bool = False, fed=None, used=None):
        cfg = self.config
        x = x + LatentAttention(cfg, cfg.kind_of(self.layer), name="self_attn")(
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
        if cfg.layer_types is not None and len(cfg.layer_types) != cfg.num_hidden_layers:
            raise ValueError(f"layer_types names {len(cfg.layer_types)} layers of "
                             f"{cfg.num_hidden_layers}")
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
            x = DeepseekV3Block(cfg, i >= cfg.first_k_dense_replace, i, name=f"layers_{i}")(
                x, decode, fed, used)
        x = RMSNorm(cfg, name="norm")(x)
        return _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head")(x)
