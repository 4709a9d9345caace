"""Laguna-XS.2 as published (``poolside/Laguna-XS.2``'s ``config.json``,
``model_type`` ``laguna``): token embedding, pre-norm residual blocks of
grouped-query softmax attention (full or sliding-window by layer, a different
number of query heads by layer over the same key heads, one sigmoid gate a
head) and a feed-forward that is dense SwiGLU in the leading layer and a
sparse mixture of SwiGLU experts with one shared expert in the others, a final
RMSNorm and an output head that is *not* tied to the embedding. Plain
``jax.numpy`` in float32 at the highest matmul precision; no cache, no
batching tricks, no kernels, and nothing imported from the package under test.

Block ``l``, for the residual stream ``x`` [B, L, E]:

* ``h = rms_norm(x)``; ``q, k, v = h @ Wq, h @ Wk, h @ Wv`` (no bias): ``H_l``
  query heads of ``head_dim`` (48 on a full layer, 64 on a sliding one: the
  columns of ``Wq`` say which) over 8 key/value heads; query head ``j`` reads
  key head ``j // (H_l / 8)``. No norm on queries or keys.
* RoPE by the layer's type (:func:`rope_tables`). A **full** layer: YaRN over
  the first ``partial_rotary_factor`` = half of a head's dimensions, theta
  500,000, factor 64 over 4,096 original positions, ``beta_fast`` 64,
  ``beta_slow`` 1, cosine and sine times ``attention_factor`` 1.41589; the
  other half of the head is not rotated. A **sliding** layer: plain RoPE, theta
  10,000, the whole head. Rotate-half pairs ``(i, i + rotated / 2)``.
* softmax attention at scale ``head_dim^-1/2``, causal; a sliding layer's
  query at ``p`` sees keys ``p - window + 1 .. p`` (512 positions, its own
  included). Dense scores under a mask, a block of query rows at a time.
* the gate: ``o_j <- sigmoid(h @ Wg)_j * o_j``, one scalar a head from the
  layer's normed input, before ``Wo``; ``x = x + o @ Wo``.
* ``h = rms_norm(x)``. Layer 0 (``mlp_layer_types`` ``dense``): ``x = x +
  down(silu(gate(h)) * up(h))`` at width 8,192. A ``sparse`` layer: scores
  ``s = sigmoid(h @ Wr)`` over the 256 experts; the 8 largest; their scores
  divided by their sum and times ``moe_routed_scaling_factor`` 2.5 are the
  weights of the experts' OUTPUTS (``moe_apply_router_weight_on_input``
  false); plus the shared SwiGLU expert of every token, ungated:
  ``x = x + sum_e w_e expert_e(h) + shared(h)``. No token is dropped.

What the published ``config.json`` does not spell out is taken from the family
(the configuration file's ``assumed`` gives each with its ground): the gate a
head, ``norm_topk_prob`` true, sigmoid scores with no correction bias, no norm
on queries or keys, the shared expert ungated.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V], and
for each block ``layers.<i>.``: ``ln_attn ln_ffn`` [E], ``wq`` [E, H_l D],
``wk wv`` [E, 8 D], ``wg`` [E, H_l], ``wo`` [H_l D, E] (heads contiguous),
then either ``gate up`` [E, F] and ``down`` [F, E] (a dense layer) or ``router``
[E, experts], ``w_gate w_up`` [experts, E, W], ``w_down`` [experts, W, E],
``shared_gate shared_up`` [E, W], ``shared_down`` [W, E].

Departures from the publication: none in the mathematics. Every token goes
through every expert and the result is masked by the routing weights (a loop
over the 256; the publication gathers each expert's tokens, which gives the
same sum). Matrices are stored input-major, ``x @ W``.
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: query rows whose dense scores are made at a time
ROW_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer type's entry of the published ``rope_parameters``."""
    theta: float
    partial_rotary_factor: float = 1.0
    factor: Optional[float] = None            # None: ``rope_type`` default
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What no weight's shape gives."""
    layer_types: Tuple[str, ...]              # "full_attention" | "sliding_attention"
    head_dim: int
    window: int
    top_k: int
    routed_scale: float
    eps: float
    rope_full: Rope
    rope_sliding: Rope
    # the sliding layers attend every earlier position (a control: the
    # configuration's masks are the program's to get wrong, not this one's)
    window_off: bool = False

    @property
    def n_layer(self):
        return len(self.layer_types)


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def yarn_inverse_frequencies(rope, head_dim):
    """The rotated pairs' inverse frequencies, float64 [rotated / 2]. Plain:
    ``theta^(-2i / rotated)``. YaRN (Peng et al. 2023, as ``transformers``
    computes it): pair ``i`` makes ``original theta^(-2i/rotated) / 2 pi``
    turns over the original context; the pair that makes ``beta`` turns is
    ``rotated ln(original / (2 pi beta)) / (2 ln theta)``; ``low`` is that of
    ``beta_fast`` rounded down, ``high`` that of ``beta_slow`` rounded up; a
    pair's frequency is the plain one up to ``low``, the plain one over
    ``factor`` from ``high`` on, and between them the blend by ``(i - low) /
    (high - low)``."""
    rotated = int(head_dim * rope.partial_rotary_factor)
    plain = np.array([rope.theta ** (-2.0 * i / rotated) for i in range(rotated // 2)])
    if rope.factor is None:
        return plain

    def pair_of(beta):
        return rotated * math.log(rope.original_max_position_embeddings / (2 * math.pi * beta)) \
            / (2 * math.log(rope.theta))

    low = max(math.floor(pair_of(rope.beta_fast)), 0)
    high = min(math.ceil(pair_of(rope.beta_slow)), rotated - 1)
    span = max(high - low, 1e-3)
    blend = np.clip((np.arange(rotated // 2) - low) / span, 0.0, 1.0)   # 0: plain, 1: divided
    return plain * (1.0 - blend) + plain / rope.factor * blend


def rope_tables(rope, head_dim, length):
    """``(cos, sin)`` [L, rotated] for positions 0..L-1, already times the
    ``attention_factor`` (1 where the type is default)."""
    inv = yarn_inverse_frequencies(rope, head_dim)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
    scale = rope.attention_factor if rope.factor is not None else 1.0
    cos = np.concatenate([np.cos(angles), np.cos(angles)], axis=-1) * scale
    sin = np.concatenate([np.sin(angles), np.sin(angles)], axis=-1) * scale
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def rotate(x, rope):
    """``x`` [B, H, L, D] at positions 0..L-1: the first ``rotated`` dimensions
    turned, rotate-half, the rest as they are."""
    d, l = x.shape[-1], x.shape[-2]
    cos, sin = rope_tables(rope, d, l)
    rotated = cos.shape[-1]
    t, rest = x[..., :rotated], x[..., rotated:]
    t1, t2 = t[..., : rotated // 2], t[..., rotated // 2:]
    turned = t * cos + jnp.concatenate([-t2, t1], axis=-1) * sin
    return jnp.concatenate([turned, rest], axis=-1)


def embed(params, ids):
    """[B, L] token ids -> [B, L, E] residual stream."""
    return _f32(params["embed"])[ids]


def block_params(params, i):
    """Block ``i``'s own weights, under their names without the prefix."""
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def attention(bp, x, sizes, layer):
    """``x + Attn_l(rms_norm(x))`` for block ``layer``."""
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, e = x.shape
        d = sizes.head_dim
        sliding = sizes.layer_types[layer] == "sliding_attention"
        rope = sizes.rope_sliding if sliding else sizes.rope_full
        h = rms_norm(x, p("ln_attn"), sizes.eps)
        q, k, v = h @ p("wq"), h @ p("wk"), h @ p("wv")
        n_q, n_kv = q.shape[-1] // d, k.shape[-1] // d
        heads = lambda t, n: t.reshape(b, l, n, d).transpose(0, 2, 1, 3)  # noqa: E731
        q, k, v = heads(q, n_q), heads(k, n_kv), heads(v, n_kv)
        q, k = rotate(q, rope), rotate(k, rope)
        # query head j reads key head j // (n_q / n_kv)
        k, v = (jnp.repeat(t, n_q // n_kv, axis=1) for t in (k, v))
        at = jnp.arange(l)
        outs = []
        for first in range(0, l, ROW_BLOCK):
            rows = at[first:first + ROW_BLOCK]
            seen = at[None, :] <= rows[:, None]
            if sliding and not sizes.window_off:
                seen = seen & (rows[:, None] - at[None, :] < sizes.window)
            scores = q[:, :, first:first + ROW_BLOCK] @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
            scores = jnp.where(seen, scores, -jnp.inf)
            outs.append(jax.nn.softmax(scores, axis=-1) @ v)
        o = jnp.concatenate(outs, axis=2)                         # [B, H, L, D]
        o = o * jax.nn.sigmoid(h @ p("wg")).transpose(0, 2, 1)[..., None]
        return x + o.transpose(0, 2, 1, 3).reshape(b, l, n_q * d) @ p("wo")


def swiglu(h, gate, up, down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def router(bp, h, sizes):
    """Routing weights [B, L, experts]: of the ``top_k`` largest sigmoid
    scores, each over their sum and times ``routed_scale``; zero elsewhere."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h @ _f32(bp["router"]))
    values, chosen = jax.lax.top_k(scores, sizes.top_k)
    values = values / values.sum(axis=-1, keepdims=True) * sizes.routed_scale
    return jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]) * values[..., None], axis=-2)


def experts(bp, h, weights):
    """sum_e weights[..., e] * expert_e(h): every token through every expert,
    one expert at a time."""
    def one(acc, ws):
        gate, up, down, w = ws
        return acc + swiglu(h, gate, up, down) * w[..., None], None
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (bp["w_gate"], bp["w_up"], bp["w_down"], jnp.moveaxis(weights, -1, 0)))
    return out


def feed_forward(bp, x, sizes):
    """``x + FFN_l(rms_norm(x))``: dense where the block has no router."""
    h = rms_norm(x, _f32(bp["ln_ffn"]), sizes.eps)
    if "router" not in bp:
        return x + swiglu(h, bp["gate"], bp["up"], bp["down"])
    routed = experts(bp, h, router(bp, h, sizes))
    return x + routed + swiglu(h, bp["shared_gate"], bp["shared_up"], bp["shared_down"])


def head(params, x, sizes):
    """Final RMSNorm and the untied output head: [B, L, E] -> logits [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["norm"], sizes.eps) @ _f32(params["head"])


def forward(params, ids, sizes):
    """Logits [B, L, V] for token ids [B, L]."""
    x = embed(params, ids)
    for i in range(sizes.n_layer):
        bp = block_params(params, i)
        x = feed_forward(bp, attention(bp, x, sizes, i), sizes)
    return head(params, x, sizes)
