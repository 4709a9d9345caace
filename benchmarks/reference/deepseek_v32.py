"""DeepSeek-V3.2's language model as its ``config.json`` describes it
(deepseek-ai/DeepSeek-V3.2, ``model_type`` ``deepseek_v32``): a token
embedding, pre-norm residual blocks of multi-head latent attention under an
indexer's selection and a SwiGLU feed-forward layer, a final RMSNorm and an
untied head. Plain ``jax.numpy`` in float32 at the highest matmul precision;
no cache, no kernels, no chunks: dense scores, the index score of every pair,
a stable sort, a mask; the route written out with a sort. Nothing is imported
from the package under test.

For ``x`` [B, L, E] the residual stream, ``h = rms_norm(x)``, eps 1e-6, no
bias but the indexer's LayerNorm:

* latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1): ``c_q =
  rms_norm(h @ W_qa)``; ``[q_nope ; q_rope] = c_q @ W_qb`` a head; ``[c ; k_r]
  = h @ W_kva``; ``c_kv = rms_norm(c)``; ``[k_nope ; v] = c_kv @ W_kvb`` a
  head; ``q_rope`` and the one ``k_r`` all heads share rotated by position,
  pairs ``(2i, 2i+1)``, by YaRN's frequencies (:func:`yarn_frequencies`); ``s =
  (q_nope . k_nope + q_rope . k_r) x softmax_scale``, ``softmax_scale =
  m(mscale_all_dim)^2 / sqrt(d_nope + d_rope)``, ``m(s) = 0.1 s ln(factor) +
  1``; softmax over the ALLOWED keys; ``x + concat_h(softmax(s) v_h) @ W_o``.
* every layer allows ``s <= t`` and ``s`` in ``Top(t)``: the ``index_topk``
  positions at or before ``t`` with the largest ``I(t, s) = sum_j w_j(t)
  relu(qI_j(t) . kI(s))`` (the release's ``inference/model.py`` ``Indexer``),
  all of them while there are fewer, equal scores to the lower position; ``qI =
  rope(c_q @ WI_q)`` a head of ``index_head_dim``, ``kI = rope(layer_norm(h @
  WI_k))`` one a position, the first ``d_rope`` values of each rotated
  HALF-SPLIT, pairs ``(i, i + d_rope / 2)``, by the same frequencies; ``w = h @
  WI_w / sqrt(index_n_heads) / sqrt(index_head_dim)``.
* feed-forward: dense SwiGLU in the first ``n_dense`` layers; after, ``s =
  sigmoid(h @ W_r)`` over all the experts; ``c = s + bias``; the experts in
  ``n_group`` groups of consecutive experts, a group's score the sum of its two
  largest ``c``; the ``topk_group`` groups of largest score kept (ties: the
  lower group), every other group's ``c`` to minus infinity; the ``k`` largest
  ``c`` (ties: the lower index); weights ``s`` of those over their sum times the
  scale; SwiGLU experts, one shared expert (DeepSeek-V3, arXiv:2412.19437
  section 2.1.2, node-limited routing).

**A chip's share**: ``Sizes.experts_first`` and the number of experts the
weights hold say which experts are here; the router scores all of them and
the sum runs over the held ones only. Table and head are the held slice of
the vocabulary.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V], and
under ``layers.<i>.``: ``ln1`` ``ln2``, ``q_a`` [E, Rq], ``q_a_norm``, ``q_b``
[Rq, H, dn + dr], ``kv_a`` [E, R + dr], ``kv_a_norm``, ``kv_b`` [R, H, dn +
dv], ``wo`` [H, dv, E], ``idx_q`` [Rq, J, d], ``idx_k`` [E, d], ``idx_k_norm``
``idx_k_norm_bias`` [d], ``idx_w`` [E, J]; then ``gate`` ``up`` ``down`` |
``router``, ``router_bias``, ``w_gate`` ``w_up`` ``w_down`` [held, ...],
``shared_gate`` ``shared_up`` ``shared_down``.

Departures from the release, each at its line: every token goes through every
held expert and the result is masked by the routing weights; the indexer's
Hadamard rotation of ``qI`` and ``kI`` (orthogonal, on both sides: every dot
product unchanged where neither is then rounded to fp8) and its fp8 storage
and scales are left out; the release applies YaRN's blend only where the
served context passes the original one (always, at the published 163,840);
matrices are stored input-major. The multi-token-prediction layer is not the
language model's forward pass and is not here.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: query rows whose scores are in flight at once
QUERY_BLOCK = 64


class Yarn(NamedTuple):
    factor: float
    original: int         # original_max_position_embeddings
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float

    def m(self, by):
        return 0.1 * by * np.log(self.factor) + 1.0 if self.factor > 1 else 1.0


class Sizes(NamedTuple):
    n_layer: int
    n_dense: int                  # first_k_dense_replace
    d_nope: int
    d_rope: int
    rank: int                     # kv_lora_rank
    theta: float
    index_top_k: int
    top_k: int                    # experts a token
    n_group: int
    topk_group: int
    routed_scale: float
    yarn: Optional[Yarn] = None
    experts_first: int = 0
    eps: float = 1e-6
    index_eps: float = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def layer_norm(x, w, bias, eps):
    x = _f32(x)
    x = x - x.mean(axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w) + _f32(bias)


def yarn_frequencies(d, theta, yarn):
    """``(f' [d / 2] float64, the factor on cosine and sine)``: the closed
    form. ``f_i = theta^(-2i/d)``; pair ``i`` makes ``original f_i / 2 pi``
    turns over the original context, and ``d(r) = d ln(original / (2 pi r)) /
    (2 ln theta)`` is the pair that makes ``r``; ``low = floor(d(beta_fast))``,
    ``high = ceil(d(beta_slow))`` (inside 0 .. d - 1); ``ramp_i = clip((i -
    low) / (high - low), 0, 1)``; ``f'_i = f_i (1 - ramp_i) + f_i / factor
    ramp_i``. The factor is ``m(mscale) / m(mscale_all_dim)``."""
    f = np.float64(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if yarn is None:
        return f, 1.0
    pair = lambda r: d * np.log(yarn.original / (2 * np.pi * r)) / (2 * np.log(theta))  # noqa: E731
    low, high = max(np.floor(pair(yarn.beta_fast)), 0), min(np.ceil(pair(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001      # the release's guard against a division by zero
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return f * (1 - ramp) + f / yarn.factor * ramp, yarn.m(yarn.mscale) / yarn.m(yarn.mscale_all_dim)


def softmax_scale(sizes):
    scale = 1.0 / np.sqrt(np.float64(sizes.d_nope + sizes.d_rope))
    return scale * (sizes.yarn.m(sizes.yarn.mscale_all_dim) ** 2 if sizes.yarn else 1.0)


def rope(x, positions, sizes, interleaved=True):
    """``x`` [..., L, d] turned by ``positions`` [L] x ``f'``: neighbours
    ``(2i, 2i+1)`` one complex number, or, not ``interleaved``, ``(i, i + d/2)``."""
    d = x.shape[-1]
    freq, factor = yarn_frequencies(d, sizes.theta, sizes.yarn)
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)  # [L, d/2]
    turn = jax.lax.complex(jnp.cos(angle), jnp.sin(angle)) * jnp.float32(factor)
    if interleaved:
        pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
        z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * turn
        return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    z = jax.lax.complex(x[..., :d // 2], x[..., d // 2:]) * turn
    return jnp.concatenate([z.real, z.imag], axis=-1)


def embed(params, ids):
    return _f32(params["embed"])[ids]


def block_params(params, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def indexer(bp, h, c_q, sizes, pos):
    """The indexer's three: ``qI`` [b, J, L, d], ``kI`` [b, L, d], ``w`` [b, L, J]."""
    p = lambda name: _f32(bp[name])  # noqa: E731
    dr = sizes.d_rope
    part = lambda t: jnp.concatenate(  # noqa: E731  the first d_rope values, half-split
        [rope(t[..., :dr], pos, sizes, interleaved=False), t[..., dr:]], axis=-1)
    q = part(jnp.einsum("blr,rjd->bjld", c_q, p("idx_q")))
    k = part(layer_norm(h @ p("idx_k"), p("idx_k_norm"), p("idx_k_norm_bias"), sizes.index_eps))
    # no Hadamard turn of q and k, no fp8 rounding of either (a departure)
    return q, k, (h @ p("idx_w")) / jnp.sqrt(jnp.float32(q.shape[1])) / jnp.sqrt(
        jnp.float32(q.shape[-1]))


def index_scores(q, k, w):
    """``I(t, s)`` [b, t, s] of the queries ``q`` [b, J, t, d] with weights
    ``w`` [b, t, J] against every key ``k`` [b, s, d]."""
    return jnp.einsum("bjts,btj->bts", jax.nn.relu(jnp.einsum("bjtd,bsd->bjts", q, k)), w)


def top_positions(scores, top_k, seen):
    """``Top(t)`` as a mask: of the positions a row has ``seen`` the ``top_k``
    with the largest score, equal scores to the lower position (a stable sort
    of the negated scores; ``jax`` sorts -0 with +0)."""
    if top_k >= scores.shape[-1]:
        return jnp.broadcast_to(seen, scores.shape)
    order = jnp.argsort(jnp.where(seen, -scores, jnp.inf), axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1)                  # each position's rank in its row
    return seen & (place < top_k)


def attention(bp, x, sizes, with_allowed=False):
    """``x`` after a layer's attention; with ``with_allowed`` also what each
    query was allowed to read, [b, L, L] bool."""
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, _ = x.shape
        dn, rank = sizes.d_nope, sizes.rank
        pos = jnp.arange(l)
        h = rms_norm(x, p("ln1"), sizes.eps)
        c_q = rms_norm(h @ p("q_a"), p("q_a_norm"), sizes.eps)
        q = jnp.einsum("blr,rhd->bhld", c_q, p("q_b"))
        joint = h @ p("kv_a")
        c_kv = rms_norm(joint[..., :rank], p("kv_a_norm"), sizes.eps)
        kv = jnp.einsum("blr,rhd->bhld", c_kv, p("kv_b"))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, sizes)
        k_rope = rope(joint[..., rank:], pos, sizes)                            # [b, l, dr]
        scale = jnp.float32(softmax_scale(sizes))

        block = min(QUERY_BLOCK, l)
        pad = -l % block

        def blocks(t):                  # [b, H, l, d] -> [blocks, b, H, block, d]
            t = jnp.pad(t, [(0, 0), (0, 0), (0, pad), (0, 0)])
            return jnp.moveaxis(t.reshape(b, t.shape[1], -1, block, t.shape[-1]), 2, 0)

        index_q, index_k, index_w = indexer(bp, h, c_q, sizes, pos)
        index = (blocks(index_q), blocks(jnp.moveaxis(index_w, -1, 1)[..., None]))

        def rows(piece):
            qn, qr, at, index = piece           # [b, H, block, .], first row's position
            t = at + jnp.arange(block)
            seen = (pos[None, :] <= t[:, None])[None]                           # [1, block, l]
            scores = index_scores(index[0], index_k, jnp.moveaxis(index[1][..., 0], 1, -1))
            seen = top_positions(scores, sizes.index_top_k, seen)
            # a padded row, past the last query, reads position 0: a finite softmax
            seen = seen | ((t >= l)[:, None] & (pos == 0)[None, :])[None]
            s = (qn @ k_nope.swapaxes(-1, -2)
                 + jnp.einsum("bhqd,bkd->bhqk", qr, k_rope)) * scale
            out = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1) @ v
            return (out, seen) if with_allowed else out

        out = jax.lax.map(rows, (blocks(q_nope), blocks(q_rope),
                                 jnp.arange(0, l + pad, block), index))
        out, may = out if with_allowed else (out, None)
        out = jnp.moveaxis(out, 0, 2).reshape(b, q.shape[1], l + pad, -1)[:, :, :l]
        out = x + jnp.einsum("bhld,hde->ble", out, p("wo"))
        if with_allowed:
            may = jnp.moveaxis(may, 0, 1).reshape(may.shape[1], l + pad, l)[:, :l]
            return out, may
        return out


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def dense(bp, x, sizes):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, bp["ln2"], sizes.eps)
        return x + swiglu(h, bp["gate"], bp["up"], bp["down"])


def route(s, bias, sizes):
    """The group-limited choice from the scores ``s`` [..., experts] and the
    selection bias: ``(chosen [..., k] int, kept [..., n_group] bool)``, both
    by a stable sort (largest first, ties to the lower index)."""
    c = s + _f32(bias)
    experts = c.shape[-1]
    grouped = c.reshape(c.shape[:-1] + (sizes.n_group, experts // sizes.n_group))
    g = -jnp.sort(-grouped, axis=-1)[..., :2].sum(axis=-1)                  # the two largest
    best = jnp.argsort(-g, axis=-1, stable=True)[..., :sizes.topk_group]
    kept = (best[..., None] == jnp.arange(sizes.n_group)).any(axis=-2)
    c = jnp.where(jnp.repeat(kept, experts // sizes.n_group, axis=-1), c, -jnp.inf)
    return jnp.argsort(-c, axis=-1, stable=True)[..., :sizes.top_k], kept


def router(bp, h, sizes):
    """Routing weights [B, L, experts] over *all* the experts: for each of
    the ``top_k`` chosen (:func:`route`) its score over the chosen scores'
    sum, times the scale; zero elsewhere."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ _f32(bp["router"]))
    chosen, _ = route(s, bp["router_bias"], sizes)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    picked = picked / picked.sum(axis=-1, keepdims=True) * sizes.routed_scale   # no + 1e-20
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]) * picked[..., None], axis=-2)


def routed(bp, h, weights, sizes):
    """The held experts' part of the layer: ``sum_e w_e swiglu_e(h)`` over the
    experts the weights hold, one expert at a time, every token through each."""
    with jax.default_matmul_precision("highest"):
        held = bp["w_gate"].shape[0]
        mine = jax.lax.dynamic_slice_in_dim(weights, sizes.experts_first, held, axis=-1)

        def one(acc, ws):
            w_gate, w_up, w_down, w = ws
            return acc + swiglu(h, w_gate, w_up, w_down) * w[..., None], None

        out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                              (bp["w_gate"], bp["w_up"], bp["w_down"],
                               jnp.moveaxis(mine, -1, 0)))
        return out


def experts(bp, x, sizes):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, bp["ln2"], sizes.eps)
        return (x + routed(bp, h, router(bp, h, sizes), sizes)
                + swiglu(h, bp["shared_gate"], bp["shared_up"], bp["shared_down"]))


def feed_forward(bp, x, sizes):
    """The layer's second half, dense or experts by what the weights hold."""
    return (experts if "router" in bp else dense)(bp, x, sizes)


def head(params, x, sizes):
    """Final RMSNorm and the untied head: [B, L, E] -> logits [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["norm"], sizes.eps) @ _f32(params["head"])


def forward(params, ids, sizes, with_allowed=False):
    """Logits [B, L, V] for token ids [B, L]; with ``with_allowed`` also each
    layer's mask of what every query read [b, L, L]."""
    x = embed(params, ids)
    masks = []
    for i in range(sizes.n_layer):
        bp = block_params(params, i)
        if with_allowed:
            x, may = attention(bp, x, sizes, with_allowed=True)
            masks.append(may)
        else:
            x = attention(bp, x, sizes)
        x = feed_forward(bp, x, sizes)
    logits = head(params, x, sizes)
    return (logits, masks) if with_allowed else logits
