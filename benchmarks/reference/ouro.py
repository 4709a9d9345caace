"""Ouro as published (Zhu et al. 2025, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741; ``ByteDance/Ouro-2.6B``'s ``config.json``,
``model_type`` ``ouro``): a looped language model. ONE stack of ``N`` decoder
layers is applied ``T`` = ``total_ut_steps`` times over the same weights; the
final RMSNorm closes every pass, and pass ``t``'s output is pass ``t + 1``'s
input. Plain ``jax.numpy`` in float32 at the highest matmul precision: a Python
loop over passes and layers, dense causal scores, RoPE written out; no cache,
no kernels, no batching tricks, and nothing imported from the package under
test.

For token ids ``ids`` [B, L]::

    h_0 = E[ids]
    for t = 1..T:                                   # one pass of the whole stack
        x = h_{t-1}
        for l = 1..N:                               # layer l's weights, the same in every pass
            x = x + RMSNorm_{l,2}( Attn_l( RMSNorm_{l,1}(x) ) )
            x = x + RMSNorm_{l,4}( SwiGLU_l( RMSNorm_{l,3}(x) ) )
        h_t = RMSNorm_f(x)                          # the final norm, after EVERY pass
        lambda_t = sigmoid(w_g . h_t + b_g)         # the exit gate, one scalar a position
    logits = h_T W_head                             # early_exit_threshold 1: all T passes
    exit pdf: p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T,  p_T = prod_{j<T} (1 - lambda_j)

``Attn_l``: ``q, k, v = h Wq, h Wk, h Wv`` (no bias), ``heads`` heads of
``E / heads``; RoPE, rotate-half, over the whole head at the token's position
(the same position in every pass); causal softmax of ``q k^T / sqrt(head
dim)``; the heads' outputs side by side through ``Wo``. A pass attends the keys
and values of ITS OWN stream: nothing of pass ``t`` is seen by pass ``t'``
except through ``h_t``. ``SwiGLU_l``: ``Wd (silu(Wg h) * Wu h)``. ``RMSNorm``:
``x / sqrt(mean(x^2) + eps) * w``.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V],
``gate_w`` [E], ``gate_b`` [] and for each layer ``layers.<l>.``: ``ln1 ln2 ln3
ln4`` [E] (before attention, after it, before the MLP, after it), ``wq wk wv
wo`` [E, E] (columns of q / k / v: heads contiguous; rows of wo likewise),
``gate up`` [E, F], ``down`` [F, E].

Departures from the publication: none is intended. What ``config.json`` does
not spell out (the sandwich norms, the final norm inside the loop, the gate's
form, no bias, a cache a pass) is written down with its ground in the
configuration file's ``assumed``. Matrices are stored input-major, ``x @ W``,
where a released checkpoint stores ``W^T``.
"""

import typing

import jax
import jax.numpy as jnp


class Sizes(typing.NamedTuple):
    """What no weight's shape gives."""
    n_head: int
    passes: int
    eps: float
    rope_theta: float


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x, theta):
    """Rotary position embedding of ``x`` [B, H, L, D] at positions 0..L-1:
    the pairs (i, i + D/2) rotate by position / theta^(2i/D)."""
    d, l = x.shape[-1], x.shape[-2]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def embed(params, ids):
    """[B, L] token ids -> [B, L, E]: ``h_0``."""
    return _f32(params["embed"])[ids]


def block_params(params, i):
    """Layer ``i``'s own weights, under their names without the prefix."""
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def attention(bp, x, sizes):
    """``x + RMSNorm_2(Attn(RMSNorm_1(x)))`` for the stream ``x`` [B, L, E]."""
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, e = x.shape
        heads = sizes.n_head
        h = rms_norm(x, p("ln1"), sizes.eps)
        q, k, v = (t.reshape(b, l, heads, e // heads).transpose(0, 2, 1, 3)
                   for t in (h @ p("wq"), h @ p("wk"), h @ p("wv")))
        q, k = rope(q, sizes.rope_theta), rope(k, sizes.rope_theta)
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(e // heads))
        scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        out = attn.transpose(0, 2, 1, 3).reshape(b, l, e) @ p("wo")
        return x + rms_norm(out, p("ln2"), sizes.eps)


def feed_forward(bp, x, sizes):
    """``x + RMSNorm_4(SwiGLU(RMSNorm_3(x)))``."""
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        h = rms_norm(x, p("ln3"), sizes.eps)
        out = (jax.nn.silu(h @ p("gate")) * (h @ p("up"))) @ p("down")
        return x + rms_norm(out, p("ln4"), sizes.eps)


def close_pass(params, x, sizes):
    """``(h_t, lambda_t)``: the final norm that closes a pass, and the exit
    gate's sigmoid a position [B, L]."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, params["norm"], sizes.eps)
        return h, jax.nn.sigmoid(h @ _f32(params["gate_w"]) + _f32(params["gate_b"]))


def head(params, h):
    """The untied output head on the last pass's ``h_T``: [B, L, E] -> [B, L, V]."""
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["head"])


def exit_pdf(gates):
    """``gates`` [T, B, L] (``lambda_t``) -> the exit distribution [B, L, T]."""
    stay, pdf = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        pdf.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(pdf + [stay], axis=-1)


def n_layers(params):
    return 1 + max(int(k.split(".")[1]) for k in params if k.startswith("layers."))


def forward(params, ids, sizes, with_exit_pdf=False):
    """Logits [B, L, V] for token ids [B, L]; with ``with_exit_pdf`` also the
    exit distribution [B, L, T]."""
    h, gates = embed(params, ids), []
    for _ in range(sizes.passes):
        x = h
        for i in range(n_layers(params)):
            bp = block_params(params, i)
            x = feed_forward(bp, attention(bp, x, sizes), sizes)
        h, lam = close_pass(params, x, sizes)
        gates.append(lam)
    logits = head(params, h)
    return (logits, exit_pdf(jnp.stack(gates))) if with_exit_pdf else logits
