"""Nemotron-H's language model as published (``model_type`` ``nemotron_h``:
the ``config.json`` of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 and the
released ``modeling_nemotron_h.py``; the Mamba-2 layer after Dao & Gu 2024,
"Transformers are SSMs", the router after DeepSeek-V3): a token embedding,
pre-norm residual layers chosen one letter a layer by
``hybrid_override_pattern``, a final RMSNorm and an untied output head.
Plain ``jax.numpy`` in float32 at the highest matmul precision; no cache,
no batching, no kernels, no chunked scan, and nothing imported from the
package under test.

Every layer is ``x <- x + mixer(rms_norm(x))``, eps 1e-5, no bias except
the convolution's. For the residual stream ``x`` [B, L, E]:

* ``M``, Mamba-2: ``[z, xBC, dt] = h @ W_in``; ``xBC <- silu(conv(xBC))``, a
  causal depthwise convolution of width 4 with a bias, over the ``x``, ``B``
  and ``C`` channels together; ``dt <- softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head; each head ``h`` keeps a state ``S`` [P, N] and
  reads the ``B`` / ``C`` of group ``h // (heads / groups)``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``,
  **one position after another** (``jax.lax.scan`` over time: the
  recurrence itself, not the chunked form a system would run);
  ``y <- rms_norm over groups of inner / groups (y * silu(z)) * w``;
  ``x + y @ W_out``.
* ``*``, attention: causal softmax attention with grouped key/value heads,
  scores over sqrt(head dim), no bias and **no positional embedding**: the
  released implementation rotates nothing (``rope_theta`` and
  ``partial_rotary_factor`` of the config are read by no code).
* ``E``, experts in a latent space: ``s = sigmoid(h @ W_r)`` in float32
  over all the experts; the ``k`` chosen are the largest of
  ``s + e_score_correction_bias`` (``n_group`` = ``topk_group`` = 1: no group
  limit); their weights are ``s`` (without the bias) divided by their sum
  and times ``routed_scaling_factor``; ``l = h @ W_down``; expert ``e`` is
  ``relu(l @ W1_e)^2 @ W2_e`` (not gated);
  ``x + (sum_e w_e expert_e(l)) @ W_up + relu(h @ W1_s)^2 @ W2_s``, the
  shared expert on the full hidden state.

**A chip's share.** ``Sizes.experts_first`` and the number of experts the
weights hold say which experts are here: the router scores all of them and
the sum runs over the held ones only; what the absent experts would add is
left out, as in the program (the latent projections and the shared expert
are every chip's, whole). ``vocab_slice`` does the same for the table and
the head: rows outside the slice embed to zero (another chip's to add) and
the logits are the slice's.

Weights are a flat dict: ``embed`` [V, E], ``norm`` [E], ``head`` [E, V],
and for layer ``i`` under ``layers.<i>.``: ``ln`` [E] and, by its letter,
``in_proj`` [E, 2I + 2GN + H], ``conv_w`` [4, I + 2GN], ``conv_b``,
``dt_bias`` ``A_log`` ``D`` [H], ``norm_w`` [I], ``out_proj`` [I, E] |
``wq`` [E, heads x D], ``wk`` ``wv`` [E, kv heads x D], ``wo`` | ``router``
[E, experts], ``router_bias`` [experts], ``latent_down`` [E, Z],
``latent_up`` [Z, E], ``w1`` [held, Z, F], ``w2`` [held, F, Z],
``shared_w1`` [E, Fs], ``shared_w2`` [Fs, E].

Departures from the publication, each at its line: every token goes
through every held expert and the result is masked by the routing weights
(a loop; the release gathers each expert's tokens: the same sum); the
normalisation divides by the sum without the release's ``+ 1e-20``;
``dt`` is not clamped (the release clamps to ``time_step_limit`` =
(0, inf), which changes nothing); matrices are stored input-major,
``x @ W``. The multi-token-prediction module is not part of the language
model's logits and is not here.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp

EPS = 1e-5          # layer_norm_epsilon / norm_eps of the published config.json


class Sizes(NamedTuple):
    """What no weight's shape gives."""
    pattern: str                 # hybrid_override_pattern, one letter a layer
    n_head: int
    n_kv_head: int
    mamba_head_dim: int
    n_groups: int
    top_k: int
    routed_scale: float
    experts_first: int = 0       # the first expert held here


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * _f32(w)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def embed(params, ids, vocab_slice=None):
    """[B, L] token ids -> [B, L, E]. With ``vocab_slice`` = (first, count)
    over a whole table, ids outside the slice embed to zero."""
    table = _f32(params["embed"])
    if vocab_slice is None:
        return table[ids]
    first, count = vocab_slice
    here = (ids >= first) & (ids < first + count)
    return jnp.where(here[..., None], table[jnp.clip(ids, first, first + count - 1)], 0.0)


def block_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def mamba(bp, x, sizes, final_state=False):
    """``final_state``: also return each head's state after the last
    position, [B, H, P, N] (what a server carries on from there)."""
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, _ = x.shape
        heads, groups, hd = bp["dt_bias"].shape[0], sizes.n_groups, sizes.mamba_head_dim
        inner = heads * hd
        state = (bp["conv_w"].shape[1] - inner) // (2 * groups)
        width = bp["conv_w"].shape[0]
        z, xbc, dt = jnp.split(rms_norm(x, p("ln")) @ p("in_proj"),
                               [inner, 2 * inner + 2 * groups * state], axis=-1)
        # causal: position t sees t-3 .. t, zeros before the sequence
        padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(padded[:, j:j + l] * p("conv_w")[j] for j in range(width))
                          + p("conv_b"))
        xs, bm, cm = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
        xs = xs.reshape(b, l, heads, hd)
        # head h reads group h // (heads / groups)
        bm = jnp.repeat(bm.reshape(b, l, groups, state), heads // groups, axis=2)
        cm = jnp.repeat(cm.reshape(b, l, groups, state), heads // groups, axis=2)
        dt = jax.nn.softplus(dt + p("dt_bias"))          # not clamped: the limit is (0, inf)
        a = -jnp.exp(p("A_log"))

        def step(s, at):
            x_t, b_t, c_t, dt_t = at                      # [b, H, P] [b, H, N] [b, H, N] [b, H]
            s = (jnp.exp(dt_t * a)[..., None, None] * s
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

        s_end, y = jax.lax.scan(step, jnp.zeros((b, heads, hd, state), jnp.float32),
                                tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt)))
        y = jnp.moveaxis(y, 0, 1) + p("D")[:, None] * xs
        y = y.reshape(b, l, inner) * jax.nn.silu(z)
        y = y.reshape(b, l, groups, inner // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + EPS)
        out = x + (y.reshape(b, l, inner) * p("norm_w")) @ p("out_proj")
        return (out, s_end) if final_state else out


def attention(bp, x, sizes):
    with jax.default_matmul_precision("highest"):
        p = lambda name: _f32(bp[name])  # noqa: E731
        b, l, _ = x.shape
        h = rms_norm(x, p("ln"))
        heads = lambda t, n: t.reshape(b, l, n, -1).transpose(0, 2, 1, 3)  # noqa: E731
        q = heads(h @ p("wq"), sizes.n_head)
        k = heads(h @ p("wk"), sizes.n_kv_head)
        v = heads(h @ p("wv"), sizes.n_kv_head)
        k, v = (jnp.repeat(t, sizes.n_head // sizes.n_kv_head, axis=1) for t in (k, v))
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(q.shape[-1]))   # no rotation
        scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        return x + attn.transpose(0, 2, 1, 3).reshape(b, l, -1) @ p("wo")


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
    """The held experts' part of the layer in the model's space:
    ``(sum_e w_e expert_e(h @ W_down)) @ W_up`` over the experts the
    weights hold, one expert at a time, every token through each."""
    with jax.default_matmul_precision("highest"):
        held = bp["w1"].shape[0]
        mine = jax.lax.dynamic_slice_in_dim(weights, sizes.experts_first, held, axis=-1)
        latent = h @ _f32(bp["latent_down"])

        def one(acc, ws):
            w1, w2, w = ws
            return acc + (relu2(latent @ _f32(w1)) @ _f32(w2)) * w[..., None], None

        out, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                              (bp["w1"], bp["w2"], jnp.moveaxis(mine, -1, 0)))
        return out @ _f32(bp["latent_up"])


def shared(bp, h):
    with jax.default_matmul_precision("highest"):
        return relu2(h @ _f32(bp["shared_w1"])) @ _f32(bp["shared_w2"])


def experts(bp, x, sizes):
    h = rms_norm(x, _f32(bp["ln"]))
    return x + routed(bp, h, router(bp, h, sizes), sizes) + shared(bp, h)


def block(bp, x, kind, sizes):
    """One layer of letter ``kind``, weights ``bp`` (:func:`block_params`)."""
    return {"M": mamba, "*": attention, "E": experts}[kind](bp, x, sizes)


def head(params, x, vocab_slice=None):
    """Final RMSNorm and the untied head: [B, L, E] -> logits [B, L, V], the
    slice's columns with ``vocab_slice`` = (first, count)."""
    with jax.default_matmul_precision("highest"):
        w = _f32(params["head"])
        if vocab_slice is not None:
            w = w[:, vocab_slice[0]:vocab_slice[0] + vocab_slice[1]]
        return rms_norm(x, params["norm"]) @ w


def forward(params, ids, sizes, vocab_slice=None):
    """Logits [B, L, V] for token ids [B, L]."""
    x = embed(params, ids, vocab_slice)
    for i, kind in enumerate(sizes.pattern):
        x = block(block_params(params, i), x, kind, sizes)
    return head(params, x, vocab_slice)
