"""SmallThinker-21BA3B as published (PowerInfer/SmallThinker-21BA3B-Instruct's
``config.json`` and ``modeling_smallthinker.py``; Song et al. 2025,
"SmallThinker: A Family of Efficient Large Language Models Natively Trained
for Local Deployment"): token embedding, pre-norm residual blocks of
grouped-query causal self-attention and a sparse mixture of ReGLU experts
whose router reads the block's INPUT, a final RMSNorm and an untied output
head. Plain ``jax.numpy`` in float32 at the highest matmul precision; dense
scores with the mask written out, a loop over the experts, no kernel, no
sort, no cache, and nothing imported from the package under test.

A block ``l``, for the residual stream ``x`` [B, L, E] at its input (every
projection bias-free):

* ``r = x @ Wr``: the router's logits, one an expert, read from the block's
  input **before** the input norm and before attention;
* ``a = rms_norm(x)``; ``q = a @ Wq`` (``H`` heads of ``D``), ``k = a @ Wk``,
  ``v = a @ Wv`` (``Hk`` heads of ``D``; ``H * D`` need not be ``E``). Where
  ``rotary[l]``: RoPE (theta 1.5e6, the pairs ``(i, i + D/2)``) on ``q`` and
  ``k``; else no positional encoding. Query head ``h`` reads key head
  ``h // (H / Hk)``; scale ``D ** -0.5``; a score is live where
  ``k_pos <= q_pos`` and, where ``windowed[l]``, ``k_pos > q_pos - window``.
  ``h = x + softmax(scores) v @ Wo``;
* ``n = rms_norm(h)``; ``S`` = the ``top_k`` largest of ``r`` (ties to the
  lower index); ``w = softmax(r[S])`` (a softmax over every expert
  renormalised over the chosen is the same numbers);
  ``y = h + sum_{e in S} w_e * down_e(relu(gate_e(n)) * up_e(n))``. Every
  block is an expert block; no shared expert; no token is dropped.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V], and
for each block ``layers.<i>.``: ``ln_attn ln_ffn`` [E], ``wq`` [E, H, D],
``wk wv`` [E, Hk, D], ``wo`` [H, D, E], ``router`` [E, experts], ``gate up``
[held, E, F], ``down`` [held, F, E].

Departures from the published description:

* ``held_first``: the bank may hold only ``held`` of the router's experts,
  those from ``held_first`` on (one chip's share of an expert-parallel
  layer). The router still scores and chooses among all of them; what an
  absent expert would add is left out, as the program under test leaves it
  to the chip that holds it. With every expert held this is the publication.
* Every token goes through every held expert and the result is weighted by
  the routing weights, zero where the expert was not chosen (the
  publication gathers each expert's tokens: the same sum).
* The ``top_k`` are found by ``top_k`` rounds of arg-max, not a sort.
* Queries are attended ``q_block`` at a time against every key (the mask
  written out for the block): the same numbers a query at a time, at a
  memory the long sequences allow. Blocks and experts are wrapped in
  ``jax.checkpoint``, which changes no value.
* Matrices are stored input-major, ``x @ W``, where the checkpoint stores
  ``W^T``. The secondary experts and the sparsity predictor of the family's
  larger siblings do not exist in this model's config and are not built.
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    """The sizes no weight's shape gives."""
    top_k: int
    window: int
    windowed: Tuple[int, ...]       # a layer: 1 = attends its window, 0 = all
    rotary: Tuple[int, ...]         # a layer: 1 = RoPE, 0 = none
    theta: float = 1.5e6
    eps: float = 1e-6
    held_first: int = 0
    q_block: Optional[int] = None   # None: every query at once


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x, theta):
    """Rotary position embedding of ``x`` [..., L, D] at positions 0..L-1:
    the pairs (i, i + D/2) rotate by position / theta^(2i/D)."""
    d, l = x.shape[-1], x.shape[-2]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def embed(params, ids):
    """[B, L] token ids -> [B, L, E] residual stream."""
    return _f32(params["embed"])[ids]


def block_params(params, i):
    """Block ``i``'s own weights, under their names without the prefix."""
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def router(bp, x, top_k):
    """Routing weights [B, L, experts] from the block's input ``x``: the
    softmax over the ``top_k`` largest logits at their experts, zero
    elsewhere. The largest are taken one at a time, ties to the lower index."""
    with jax.default_matmul_precision("highest"):
        logits = x @ _f32(bp["router"])
    chosen = jnp.zeros(logits.shape, bool)
    for _ in range(top_k):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, logits), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, logits.shape[-1], dtype=bool)
    return jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)


def attention(bp, x, spec, layer):
    """``x + softmax(mask(q k^T / sqrt(D))) v @ Wo`` for block ``layer``."""
    with jax.default_matmul_precision("highest"):
        b, l, _ = x.shape
        a = rms_norm(x, bp["ln_attn"], spec.eps)
        q = jnp.einsum("ble,ehd->bhld", a, _f32(bp["wq"]))
        k = jnp.einsum("ble,ehd->bhld", a, _f32(bp["wk"]))
        v = jnp.einsum("ble,ehd->bhld", a, _f32(bp["wv"]))
        if spec.rotary[layer]:
            q, k = rope(q, spec.theta), rope(k, spec.theta)
        heads, kv_heads, d = q.shape[1], k.shape[1], q.shape[-1]
        q = q.reshape(b, kv_heads, heads // kv_heads, l, d)     # key head major
        q_block = spec.q_block or l
        k_pos = jnp.arange(l)

        @jax.checkpoint
        def attend(q_part, first):
            """``q_part`` [B, Hk, rep, Q, D], queries ``first``... on: the
            scores against every key, the mask written out."""
            q_pos = first + jnp.arange(q_part.shape[-2])
            live = k_pos[None, :] <= q_pos[:, None]
            if spec.windowed[layer]:
                live = live & (k_pos[None, :] > q_pos[:, None] - spec.window)
            scores = jnp.einsum("bgrqd,bgkd->bgrqk", q_part, k) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bgrqk,bgkd->bgrqd", probs, v)

        parts = q.reshape(b, kv_heads, heads // kv_heads, l // q_block, q_block, d)
        out = jax.lax.map(lambda xs: attend(*xs),
                          (jnp.moveaxis(parts, 3, 0), jnp.arange(0, l, q_block)))
        out = jnp.moveaxis(out, 0, 3).reshape(b, heads, l, d)
        return x + jnp.einsum("bhld,hde->ble", out, _f32(bp["wo"]))


def experts(bp, n, weights, held_first=0):
    """sum over the held experts of ``weights[..., e] * down_e(relu(gate_e(n))
    * up_e(n))``: every token through every held expert, one at a time."""
    with jax.default_matmul_precision("highest"):
        held = bp["gate"].shape[0]
        mine = jnp.moveaxis(weights[..., held_first:held_first + held], -1, 0)

        @jax.checkpoint
        def one(gate, up, down, w):
            y = (jax.nn.relu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)
            return y * w[..., None]

        out, _ = jax.lax.scan(lambda acc, ws: (acc + one(*ws), None), jnp.zeros_like(n),
                              (bp["gate"], bp["up"], bp["down"], mine))
        return out


def block(bp, x, spec, layer):
    """Block ``layer``, weights ``bp`` (see :func:`block_params`), applied to
    the residual stream ``x`` [B, L, E]."""
    weights = router(bp, x, spec.top_k)
    h = attention(bp, x, spec, layer)
    n = rms_norm(h, bp["ln_ffn"], spec.eps)
    return h + experts(bp, n, weights, spec.held_first)


def head(params, x, eps=1e-6):
    """Final RMSNorm and the untied output head: [B, L, E] -> logits [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["norm"], eps) @ _f32(params["head"])


def nll(logits, ids):
    """Mean next-token cross-entropy of ``logits`` [B, L, V] for ``ids`` [B, L],
    over the vocabulary the logits span."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def n_layers(params):
    return 1 + max(int(k.split(".")[1]) for k in params if k.startswith("layers."))


def forward(params, ids, spec):
    """Logits [B, L, V] for token ids [B, L]."""
    x = embed(params, ids)
    for i in range(n_layers(params)):
        x = block(block_params(params, i), x, spec, i)
    return head(params, x, spec.eps)


def loss(params, ids, spec):
    """Mean next-token cross-entropy; its ``jax.grad`` is the reference's gradient."""
    return nll(forward(params, ids, spec), ids)
