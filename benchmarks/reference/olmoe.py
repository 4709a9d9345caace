"""OLMoE as published (Muennighoff et al. 2024, "OLMoE: Open
Mixture-of-Experts Language Models"; the released ``modeling_olmoe.py`` of
the ``transformers`` library and ``allenai/OLMoE-1B-7B-0125-Instruct``'s
``config.json``): token embedding, pre-norm residual blocks of causal
multi-head self-attention and a sparse mixture of SwiGLU experts, a final
RMSNorm and an output head that is *not* tied to the embedding. Plain
``jax.numpy`` in float32 at the highest matmul precision; no cache, no
batching tricks, no kernels, and nothing imported from the package under
test.

A block, for the residual stream ``x`` [B, L, E]:

* ``h = rms_norm(x)``; ``q, k, v = h @ Wq, h @ Wk, h @ Wv`` (no bias;
  ``clip_qkv`` is null in the published configuration);
* QK-norm: ``q`` and ``k`` go through an RMSNorm over their *whole* width
  (every head's features at once) before they are split into heads;
* RoPE, theta 10000, rotate-half, on the heads of ``q`` and ``k``;
* causal softmax attention, heads of ``head_dim`` = E / heads;
  ``x = x + attn @ Wo``;
* ``h = rms_norm(x)``; router logits ``h @ Wr`` [.., 64]; softmax over the
  experts in float32; the ``k`` largest are kept **with their softmax
  values as weights, not renormalised** (``norm_topk_prob`` false);
  ``x = x + sum_e weight_e * down_e(silu(gate_e(h)) * up_e(h))``. No token
  is dropped, whatever the load of an expert.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V],
and for each block ``layers.<i>.``: ``ln_attn ln_ffn`` [E], ``wq wk wv wo``
[E, E] (columns of q / k / v: heads contiguous; rows of wo likewise),
``q_norm k_norm`` [E], ``router`` [E, experts], ``gate up`` [experts, E, F],
``down`` [experts, F, E].

Departures from the publication: none in the mathematics. Every token goes
through every expert and the result is masked by the routing weights (a
loop over the 64; the publication gathers each expert's tokens, which
gives the same sum). Matrices are stored input-major, ``x @ W``, where the
released checkpoint stores ``W^T``. The auxiliary load-balancing and
router z-losses are training terms and are left out.
"""

import jax
import jax.numpy as jnp

EPS = 1e-5          # rms_norm_eps of the published config.json
ROPE_THETA = 10000.0


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * _f32(w)


def rope(x):
    """Rotary position embedding of ``x`` [B, H, L, D] at positions 0..L-1:
    the pairs (i, i + D/2) rotate by position / theta^(2i/D)."""
    d, l = x.shape[-1], x.shape[-2]
    inv_freq = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
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


def router(bp, h, top_k):
    """Routing weights [B, L, experts]: the softmax value of each of the
    ``top_k`` largest experts, zero elsewhere."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(h @ _f32(bp["router"]), axis=-1)
    values, chosen = jax.lax.top_k(probs, top_k)
    return jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1]) * values[..., None], axis=-2)


def attention(bp, x, n_head):
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, e = x.shape
        h = rms_norm(x, p("ln_attn"))
        q = rms_norm(h @ p("wq"), p("q_norm"))
        k = rms_norm(h @ p("wk"), p("k_norm"))
        v = h @ p("wv")
        q, k, v = (t.reshape(b, l, n_head, e // n_head).transpose(0, 2, 1, 3) for t in (q, k, v))
        q, k = rope(q), rope(k)
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(e // n_head))
        scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        return x + attn.transpose(0, 2, 1, 3).reshape(b, l, e) @ p("wo")


def experts(bp, h, weights):
    """sum_e weights[..., e] * down_e(silu(gate_e(h)) * up_e(h)): every
    token through every expert, one expert at a time."""
    with jax.default_matmul_precision("highest"):
        def one(acc, ws):
            gate, up, down, w = ws
            y = (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)
            return acc + y * w[..., None], None
        out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                              (bp["gate"], bp["up"], bp["down"], jnp.moveaxis(weights, -1, 0)))
        return out


def block(bp, x, n_head, top_k):
    """One block, weights ``bp`` (see :func:`block_params`), applied to the
    residual stream ``x`` [B, L, E]."""
    x = attention(bp, x, n_head)
    h = rms_norm(x, _f32(bp["ln_ffn"]))
    return x + experts(bp, h, router(bp, h, top_k))


def head(params, x):
    """Final RMSNorm and the untied output head: [B, L, E] -> logits [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["norm"]) @ _f32(params["head"])


def n_layers(params):
    return 1 + max(int(k.split(".")[1]) for k in params if k.startswith("layers."))


def forward(params, ids, n_head, top_k):
    """Logits [B, L, V] for token ids [B, L]."""
    x = embed(params, ids)
    for i in range(n_layers(params)):
        x = block(block_params(params, i), x, n_head, top_k)
    return head(params, x)
