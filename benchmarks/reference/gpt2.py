"""GPT-2 as published (Radford et al. 2019, "Language Models are
Unsupervised Multitask Learners"; the released ``gpt-2`` model code):
learned token and position embeddings, pre-LayerNorm blocks of causal
multi-head self-attention and a 4x GELU(tanh) MLP with residual
connections, a final LayerNorm, and an output head tied to the token
embedding. Plain ``jax.numpy`` in float32 at the highest matmul
precision; no kernels, no cache, no batching tricks, and nothing imported
from the package under test.

Weights are a flat dict under the names of the released checkpoint:
``wte`` [V, E], ``wpe`` [P, E], ``ln_f.g``/``ln_f.b`` [E], and for each
block ``h.<i>.``: ``ln_1.g ln_1.b ln_2.g ln_2.b`` [E], ``attn.c_attn.w``
[E, 3E] (columns q | k | v, heads contiguous inside each), ``attn.c_attn.b``
[3E], ``attn.c_proj.w`` [E, E], ``attn.c_proj.b`` [E], ``mlp.c_fc.w``
[E, 4E], ``mlp.c_fc.b`` [4E], ``mlp.c_proj.w`` [4E, E], ``mlp.c_proj.b`` [E].

Departures from the published model: none in the mathematics. Dropout is
absent (the benchmark's configurations set it to 0).
"""

import math

import jax
import jax.numpy as jnp

EPS = 1e-5  # layer_norm_epsilon of every released GPT-2 config.json


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, g, b):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * _f32(g) + _f32(b)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def embed(params, ids):
    """[B, L] token ids -> [B, L, E] residual stream."""
    return _f32(params["wte"])[ids] + _f32(params["wpe"])[: ids.shape[1]]


def block_params(params, i):
    """Block ``i``'s own weights, under their names without the prefix."""
    prefix = f"h.{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def block(bp, x, n_head):
    """One block, weights ``bp`` (see :func:`block_params`), applied to the
    residual stream ``x`` [B, L, E]."""
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, e = x.shape
        h = layer_norm(x, p("ln_1.g"), p("ln_1.b"))
        qkv = h @ p("attn.c_attn.w") + p("attn.c_attn.b")
        q, k, v = (t.reshape(b, l, n_head, e // n_head).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(e // n_head)
        causal = jnp.tril(jnp.ones((l, l), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        attn = attn.transpose(0, 2, 1, 3).reshape(b, l, e)
        x = x + attn @ p("attn.c_proj.w") + p("attn.c_proj.b")
        h = layer_norm(x, p("ln_2.g"), p("ln_2.b"))
        h = gelu_tanh(h @ p("mlp.c_fc.w") + p("mlp.c_fc.b"))
        return x + h @ p("mlp.c_proj.w") + p("mlp.c_proj.b")


def head(params, x):
    """Final LayerNorm and the tied output head: [B, L, E] -> logits [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, params["ln_f.g"], params["ln_f.b"]) @ _f32(params["wte"]).T


def n_layers(params):
    return 1 + max(int(k.split(".")[1]) for k in params if k.startswith("h."))


def forward(params, ids, n_head):
    """Logits [B, L, V] for token ids [B, L]."""
    x = embed(params, ids)
    for i in range(n_layers(params)):
        x = block(block_params(params, i), x, n_head)
    return head(params, x)


def nll(logits, ids):
    """Mean next-token negative log-likelihood: position t predicts t+1."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()


def loss(params, ids, n_head):
    return nll(forward(params, ids, n_head), ids)
