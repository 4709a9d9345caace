"""JoyAI-LLM-Flash's language model as its ``config.json`` describes it
(jdopensource/JoyAI-LLM-Flash; every key is the DeepSeek-V3 family's, whose
equations these are: DeepSeek-V2, arXiv:2405.04434 section 2.1 for the latent
attention, DeepSeek-V3, arXiv:2412.19437 section 2.1.2 for the router): a
token embedding, pre-norm residual blocks of multi-head latent attention and
a SwiGLU feed-forward layer (dense in the first ``first_k_dense_replace``
layers, routed experts plus one shared expert after), a final RMSNorm and an
untied head. Plain ``jax.numpy`` in float32 at the highest matmul precision;
the expanded form of the attention only; no cache, no kernels, no batching,
and nothing imported from the package under test.

For ``x`` [B, L, E] the residual stream, eps 1e-6, no bias anywhere:

* attention, ``h = rms_norm(x)``: ``c_q = rms_norm(h @ W_qa)``;
  ``[q_nope ; q_rope] = c_q @ W_qb`` a head; ``[c ; k_r] = h @ W_kva``;
  ``c_kv = rms_norm(c)``; ``[k_nope ; v] = c_kv @ W_kvb`` a head;
  ``q_rope`` and the one ``k_r`` all heads share rotated by position, the
  pairs ``(2i, 2i+1)`` as one complex number turned by ``pos *
  theta^(-2i/d_rope)`` (``rope_interleave``); ``s = (q_nope . k_nope + q_rope .
  k_r) / sqrt(d_nope + d_rope)`` (no ``mscale``: ``rope_scaling`` is null);
  causal softmax; ``x + concat_h(softmax(s) v_h) @ W_o``. Scores are made a
  block of query rows at a time (:data:`QUERY_BLOCK`), each row over all its
  keys at once: the same numbers, [heads, block, L] of them in flight.
* dense layer: ``x + (silu(h @ W_g) * (h @ W_u)) @ W_d``.
* expert layer: ``s = sigmoid(h @ W_r)`` over all the experts; the ``k``
  chosen are the largest of ``s + e_score_correction_bias`` (``n_group`` =
  ``topk_group`` = 1: no group limit); their weights are ``s`` (without the
  bias) over their sum, times ``routed_scaling_factor``; ``x + sum_e w_e
  swiglu_e(h) + swiglu_shared(h)``.

**A chip's share.** ``Sizes.experts_first`` and the number of experts the
weights hold say which experts are here: the router scores all of them and
the sum runs over the held ones only; what the absent experts would add is
left out, as in the program. The table and the head are the held slice of
the vocabulary: ids and logits are over the slice.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V], and
for layer ``i`` under ``layers.<i>.``: ``ln1`` ``ln2`` [E], ``q_a`` [E, Rq],
``q_a_norm`` [Rq], ``q_b`` [Rq, H, dn + dr], ``kv_a`` [E, R + dr],
``kv_a_norm`` [R], ``kv_b`` [R, H, dn + dv], ``wo`` [H, dv, E], and ``gate``
``up`` [E, F] ``down`` [F, E] | ``router`` [E, experts], ``router_bias``,
``w_gate`` ``w_up`` [held, E, Fe], ``w_down`` [held, Fe, E], ``shared_gate``
``shared_up`` ``shared_down``.

Departures from the release, each at its line: every token goes through
every held expert and the result is masked by the routing weights (the
release gathers each expert's tokens: the same sum); the normalisation
divides by the sum without the release's ``+ 1e-20``; matrices are stored
input-major, ``x @ W``. The multi-token-prediction module is not part of the
language model's logits and is not here.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: query rows whose scores are in flight at once
QUERY_BLOCK = 512


class Sizes(NamedTuple):
    n_layer: int
    n_dense: int          # first_k_dense_replace
    d_nope: int
    d_rope: int
    rank: int             # kv_lora_rank
    top_k: int
    routed_scale: float
    rope_theta: float
    experts_first: int = 0
    eps: float = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x, positions, theta):
    """``x`` [..., L, d] with neighbours ``(2i, 2i+1)`` one complex number,
    turned by ``positions`` [L] x ``theta^(-2i/d)``."""
    d = x.shape[-1]
    freq = jnp.asarray(1.0 / np.float64(theta) ** (np.arange(0, d, 2) / d), jnp.float32)
    turn = jnp.exp(1j * (positions.astype(jnp.float32)[:, None] * freq))       # [L, d/2]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * turn
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def embed(params, ids):
    return _f32(params["embed"])[ids]


def block_params(params, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def attention(bp, x, sizes):
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, _ = x.shape
        dn, dr, rank = sizes.d_nope, sizes.d_rope, sizes.rank
        pos = jnp.arange(l)
        h = rms_norm(x, p("ln1"), sizes.eps)
        c_q = rms_norm(h @ p("q_a"), p("q_a_norm"), sizes.eps)
        q = jnp.einsum("blr,rhd->bhld", c_q, p("q_b"))
        joint = h @ p("kv_a")
        c_kv = rms_norm(joint[..., :rank], p("kv_a_norm"), sizes.eps)
        kv = jnp.einsum("blr,rhd->bhld", c_kv, p("kv_b"))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, sizes.rope_theta)
        k_rope = rope(joint[..., rank:], pos, sizes.rope_theta)                 # [b, l, dr]
        scale = 1.0 / jnp.sqrt(jnp.float32(dn + dr))                           # no mscale

        block = min(QUERY_BLOCK, l)
        pad = -l % block

        def rows(piece):
            qn, qr, at = piece          # [b, H, block, .], first row's position
            s = (qn @ k_nope.swapaxes(-1, -2)
                 + jnp.einsum("bhqd,bkd->bhqk", qr, k_rope)) * scale
            seen = pos[None, :] <= (at + jnp.arange(block))[:, None]
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v

        def blocks(t):
            t = jnp.pad(t, [(0, 0), (0, 0), (0, pad), (0, 0)])
            return jnp.moveaxis(t.reshape(b, t.shape[1], -1, block, t.shape[-1]), 2, 0)

        out = jax.lax.map(rows, (blocks(q_nope), blocks(q_rope),
                                 jnp.arange(0, l + pad, block)))
        out = jnp.moveaxis(out, 0, 2).reshape(b, q.shape[1], l + pad, -1)[:, :, :l]
        return x + jnp.einsum("bhld,hde->ble", out, p("wo"))


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def dense(bp, x, sizes):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, bp["ln2"], sizes.eps)
        return x + swiglu(h, bp["gate"], bp["up"], bp["down"])


def router(bp, h, sizes):
    """Routing weights [B, L, experts] over *all* the experts: for each of
    the ``top_k`` chosen by score + bias its score over the chosen scores'
    sum, times the scale; zero elsewhere."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ _f32(bp["router"]))
    _, chosen = jax.lax.top_k(s + _f32(bp["router_bias"]), sizes.top_k)
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


def block(bp, x, sizes):
    return feed_forward(bp, attention(bp, x, sizes), sizes)


def head(params, x, sizes):
    """Final RMSNorm and the untied head: [B, L, E] -> logits [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["norm"], sizes.eps) @ _f32(params["head"])


def forward(params, ids, sizes):
    """Logits [B, L, V] for token ids [B, L]."""
    x = embed(params, ids)
    for i in range(sizes.n_layer):
        x = block(block_params(params, i), x, sizes)
    return head(params, x, sizes)
