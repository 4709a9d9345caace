"""The SmallThinker family: builds the package's model (a configuration of
``models/llama.py``) from a configuration file whose ``family`` is
``smallthinker``, maps the package's parameter tree onto the reference's flat
names, and holds the two sides against each other on the training path.

The reference (``benchmarks/reference/smallthinker.py``) reads the engine's
own float32 masters (no leaf is copied or reshaped here) and is run a
sequence at a time, a block to a program, queries ``Q_BLOCK`` at a time and
the head ``HEAD_BLOCK`` positions at a time. Its gradient's norm is taken A
LAYER AT A TIME: every block's input is kept for every sequence, then from
the head down one block's ``vjp`` a sequence, the sequences' gradients of
that block added, their squares summed and the gradient freed. A whole
float32 gradient (2.6 GB) beside the engine's masters and moments (7.9 GB)
and the kept inputs (1.7 GB at two sequences of 16,384) would not fit.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import smallthinker as ref

#: queries the reference attends at a time (scores [28, Q, 16384] float32 are
#: 0.47 GB at 256, and the backward holds a few of them) and positions its
#: head makes logits for at a time ([2048, 37984] float32 are 0.31 GB)
Q_BLOCK, HEAD_BLOCK = 256, 2048

#: what :func:`model` last built: the sizes of the routing and of the
#: attention pattern that no weight's shape gives; the runner hands the
#: reference the weights, the ids and ``n_head`` only
_built = {}


def spec_of(config, seq_len=None):
    """The reference's :class:`Spec` for a parsed configuration file."""
    held = config.get("experts_held") or [0, config["moe_num_primary_experts"]]
    q_block = None if seq_len is None or seq_len <= Q_BLOCK or seq_len % Q_BLOCK else Q_BLOCK
    return ref.Spec(top_k=int(config["moe_num_active_primary_experts"]),
                    window=int(config["sliding_window_size"]),
                    windowed=tuple(config["sliding_window_layout"]),
                    rotary=tuple(config["rope_layout"]), theta=float(config["rope_theta"]),
                    eps=float(config["rms_norm_eps"]), held_first=int(held[0]),
                    q_block=q_block)


def model(config, deployment, **overrides):
    """The package's SmallThinker at the sizes of ``config`` (the parsed
    configuration file, keys as published). ``deployment`` is its ``train``
    block. The router keeps every published output
    (``moe_num_primary_experts_published``); the bank holds ``experts_held``;
    ``draw`` holds the seeded draw's multipliers (:func:`scaled_draw`)."""
    from deepspeed_tpu.models.llama import LlamaConfig

    _built["spec"] = spec_of(config, overrides.get("n_positions"))
    overrides = {k: v for k, v in overrides.items() if k != "n_positions"}
    held = config.get("experts_held")
    sizes = dict(vocab_size=deployment.get("vocab_rows", config["vocab_size"]),
                 hidden_size=config["hidden_size"],
                 intermediate_size=config["moe_ffn_hidden_size"],
                 num_hidden_layers=config["num_hidden_layers"],
                 num_attention_heads=config["num_attention_heads"],
                 num_key_value_heads=config["num_key_value_heads"],
                 head_dim=config["head_dim"],
                 max_position_embeddings=config["max_position_embeddings"],
                 rms_norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
                 sliding_window=config["sliding_window_size"],
                 sliding_window_layout=tuple(config["sliding_window_layout"]),
                 rope_layout=tuple(config["rope_layout"]),
                 moe_num_experts=config.get("moe_num_primary_experts_published",
                                            config["moe_num_primary_experts"]),
                 moe_k=config["moe_num_active_primary_experts"],
                 moe_norm_topk_prob=config["norm_topk_prob"], moe_drop_tokens=False,
                 moe_layer_freq=1, moe_activation="relu", moe_router_before_attention=True,
                 moe_aux_loss_coef=0.0,
                 moe_experts_held=None if held is None else tuple(held))
    return _seeded_model(dict(config.get("draw") or {}))(LlamaConfig(**{**sizes, **overrides}))


def scaled_draw(params, draw):
    """The package's plain N(0, 0.02) draw with the kernels of the projections
    ``draw`` names (``v_proj``, ``o_proj``, ...: the module's name in the
    tree, every layer alike) multiplied by its number; powers of two, exact in
    bfloat16. A kind it leaves out stays as drawn. The configuration's
    ``assumed.weights`` says what each multiplier is for."""
    def scale(path, w):
        names = [getattr(k, "key", None) for k in path]
        by = next((draw[name] for name in names if name in draw), None)
        return w if by is None else (w * by).astype(w.dtype)
    return jax.tree_util.tree_map_with_path(scale, params)


def _seeded_model(draw):
    """The package's model class with one thing changed: ``init`` returns the
    benchmark's seeded weights (:func:`scaled_draw` over the package's own
    draw, the leaves still in their partitioning boxes, which the engine's
    plan reads). The runner makes the weights by the engine's ``model.init``
    and gives the family no later hand on them."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    if not draw:
        return LlamaForCausalLM

    class SeededSmallThinker(LlamaForCausalLM):
        def init(self, *args, **kwargs):
            variables = super().init(*args, **kwargs)
            return {**variables, "params": scaled_draw(variables["params"], draw)}

    return SeededSmallThinker


#: the flash kernels by the names their ``pallas_call``s carry into the
#: compiled program (``ops/pallas/flash_attention.py``), the longer first
FLASH_KERNELS = (("flash_bwd_dkv", "pallas:flash:dkv"), ("flash_bwd_dq", "pallas:flash:dq"),
                 ("flash_fwd", "pallas:flash:fwd"))
#: megablox's kernels are named after the jitted functions that hold them, with
#: what differentiated them around the name: ``%gmm.3``, ``%jvp_jit_gmm__.1``,
#: ``%transpose_jvp_jit_tgmm___.2``
_GROUPED = re.compile(r"(^|_)t?gmm(_|\.|$)")


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the grouped
    expert matmuls of the forward and the backward (megablox ``gmm`` and
    ``tgmm``, or XLA's ``ragged-dot``) are ``pallas:moe:matmul``; the flash
    kernels are named by their pass; any other custom call ``pallas:other``."""
    name = trace.op_name(text).lstrip("%")
    if _GROUPED.search(name) or "ragged-dot" in name:
        return "pallas:moe:matmul"
    for kernel, label in FLASH_KERNELS:
        if name.startswith(kernel):
            return label
    if trace.is_custom_call(text):
        return "pallas:other"
    return trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict: the leaves
    themselves, no view, reshape or copy."""
    flat = {"embed": params["embed_tokens"], "norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"]}
    n_layer = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n_layer):
        blk, pre = params[f"layers_{i}"], f"layers.{i}."
        att, moe = blk["self_attn"], blk["moe"]["deepspeed_moe"]
        bank = moe["experts"]["deepspeed_experts"]
        flat.update({
            pre + "ln_attn": blk["input_layernorm"]["weight"],
            pre + "ln_ffn": blk["post_attention_layernorm"]["weight"],
            pre + "wq": att["q_proj"]["kernel"], pre + "wk": att["k_proj"]["kernel"],
            pre + "wv": att["v_proj"]["kernel"], pre + "wo": att["o_proj"]["kernel"],
            pre + "router": moe["gate"]["wg"],
            pre + "gate": bank["gate_proj"]["kernel"], pre + "up": bank["up_proj"]["kernel"],
            pre + "down": bank["down_proj"]["kernel"],
        })
    return flat


def _layer_spec(spec, layer):
    """``spec`` for block ``layer`` alone (as layer 0 of a one-layer pattern),
    so that blocks of one kind share a compiled program."""
    return spec._replace(windowed=(spec.windowed[layer],), rotary=(spec.rotary[layer],))


_embed = jax.jit(ref.embed)
_block = jax.jit(ref.block, static_argnums=(2, 3))
_head = jax.jit(ref.head, static_argnums=2)


@jax.jit
def _nll_sum(top, x, ids):
    """Sum (not mean) of next-token NLL of ``x`` [B, L, E] for ``ids`` [B, L],
    the head ``HEAD_BLOCK`` positions at a time: the last position has no
    next token and counts nothing."""
    b, l, e = x.shape
    block = HEAD_BLOCK if l % HEAD_BLOCK == 0 else l
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], axis=1)
    counts = jnp.broadcast_to(jnp.arange(l) < l - 1, (b, l))

    @jax.checkpoint
    def part(xs):
        x_part, labels_part, counts_part = xs
        logp = jax.nn.log_softmax(ref.head(top, x_part), axis=-1)
        picked = jnp.take_along_axis(logp, labels_part[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(counts_part, picked, 0.0))

    split = lambda t: jnp.moveaxis(t.reshape(b, l // block, block, *t.shape[2:]), 1, 0)  # noqa: E731
    return jnp.sum(jax.lax.map(part, (split(x), split(labels), split(counts))))


_nll_sum_grad = jax.jit(jax.grad(_nll_sum, argnums=(0, 1)))


def _block_pull(bp, x, ct, spec, layer):
    """(gradient of the block's weights, of its input) for the cotangent ``ct``."""
    _, pull = jax.vjp(lambda bp_, x_: ref.block(bp_, x_, spec, layer), bp, x)
    return pull(ct)


_block_vjp = jax.jit(_block_pull, static_argnums=(3, 4))
_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_squares = jax.jit(lambda t: sum(jnp.sum(jnp.square(v)) for v in jax.tree.leaves(t)))


def _top(flat):
    """The final norm and the head (the table is :func:`_embed`'s alone)."""
    return {k: flat[k] for k in ("norm", "head")}


def _inputs(flat, part, spec):
    """The residual stream at every block's input and after the last, for
    ``part`` [b, L]."""
    xs = [_embed({"embed": flat["embed"]}, part)]
    for i in range(ref.n_layers(flat)):
        xs.append(_block(ref.block_params(flat, i), xs[-1], _layer_spec(spec, i), 0))
    return xs


def reference_logits(flat, ids, n_head):
    """Reference logits [B, L, V], a block to a program, for the
    configuration :func:`model` was last called with."""
    spec = _built["spec"]
    return _head(_top(flat), _inputs(flat, jnp.asarray(ids), spec)[-1], spec.eps)


def reference_loss(flat, ids, n_head, seqs_per_call, place=None):
    """Mean next-token loss of the reference over ``ids`` [B, L],
    ``seqs_per_call`` sequences at a time."""
    spec, total = _built["spec"], 0.0
    for at in range(0, ids.shape[0], seqs_per_call):
        part = jnp.asarray(ids[at:at + seqs_per_call])
        if place is not None:
            part = jax.device_put(part, place)
        total += float(_nll_sum(_top(flat), _inputs(flat, part, spec)[-1], part))
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def reference_grad_norm(flat, ids, n_head, seqs_per_call):
    """Global L2 norm of the gradient of the reference's mean loss over
    ``ids``, a layer at a time (the module's docstring): the sequences'
    gradients of one block are added before their squares are."""
    spec, top = _built["spec"], _top(flat)
    parts = [jnp.asarray(ids[at:at + seqs_per_call])
             for at in range(0, ids.shape[0], seqs_per_call)]
    kept = [_inputs(flat, part, spec) for part in parts]

    def summed(grads):
        total = None
        for g in grads:
            total = g if total is None else _add(total, g)
        return float(_squares(total))

    from_head = [_nll_sum_grad(top, xs.pop(), part) for xs, part in zip(kept, parts)]
    cts = [dx for _, dx in from_head]
    squares = summed(d for d, _ in from_head)
    del from_head
    for i in reversed(range(ref.n_layers(flat))):
        bp, grads = ref.block_params(flat, i), []
        for n, xs in enumerate(kept):
            d_bp, cts[n] = _block_vjp(bp, xs.pop(), cts[n], _layer_spec(spec, i), 0)
            grads.append(d_bp)
            if len(grads) == 2:
                grads = [_add(*grads)]
        squares += summed(grads)
        del grads, d_bp
    table = jnp.zeros(flat["embed"].shape, jnp.float32)
    for part, ct in zip(parts, cts):
        table = table.at[part].add(ct)
    squares += float(_squares(table))
    return float(np.sqrt(squares)) / (ids.shape[0] * (ids.shape[1] - 1))
