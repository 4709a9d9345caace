"""The Ouro family: builds the package's model (a configuration of
``deepspeed_tpu/models/llama.py``: a stack of multi-head layers with sandwich
norms, run ``total_ut_steps`` times over one set of weights, the final norm
after every pass, a cache a pass) from a configuration file whose ``family`` is
``ouro``, maps the package's parameter tree onto the reference's flat names,
and holds the two sides against each other.

The reference (``benchmarks/reference/ouro.py``) is run a sequence at a time
and a layer's half at a time through one jitted program each, every pass
anew, each weight upcast from the served leaf as it is used, and the head over
blocks of positions whose logits are gathered on the host: a float32 copy of
the weights (10.7 GB) does not fit beside the server.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import ouro as ref

#: positions whose logits the reference's head makes at a time
HEAD_BLOCK = 256


def _sizes(config):
    return ref.Sizes(n_head=int(config["num_attention_heads"]),
                     passes=int(config["total_ut_steps"]), eps=float(config["rms_norm_eps"]),
                     rope_theta=float(config["rope_theta"]))


def model(config, deployment, **overrides):
    """The package's model at the sizes of ``config`` (the parsed configuration
    file, keys as published). ``deployment`` is the ``serve`` block: parameters
    are handed over in the type they are served in, each pass of each layer holds
    ``max_out_tokens`` positions a slot, and a chunk's attention walks its
    stored pool ``decode_key_block`` positions a step where the block names
    one. ``draw`` holds the seeded draw's multipliers (:func:`scaled_draw`).
    ``early_exit_threshold`` 1 is the only one built: every position runs
    every pass."""
    from deepspeed_tpu.models.llama import LlamaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["sizes"] = _sizes(config)
    if config["early_exit_threshold"] != 1:
        raise NotImplementedError("an exit before the last pass (early_exit_threshold < 1): "
                                  "the serving programs run every pass for every position")
    if any(kind != "full_attention" for kind in config["layer_types"]) \
            or config["use_sliding_window"] or config["rope_scaling"] is not None \
            or config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise NotImplementedError("this family: full attention, plain RoPE, SwiGLU, untied head")
    same = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "max_position_embeddings",
            "rms_norm_eps")
    walk = deployment.get("decode_key_block")
    sizes = dict({key: config[key] for key in same}, rope_theta=float(config["rope_theta"]),
                 loop_passes=int(config["total_ut_steps"]), sandwich_norm=True,
                 decode_key_block=walk,
                 # the head over 49,152 rows for the one position a slot a chunk keeps
                 head_last_fed_only=walk is not None,
                 decode_cache_len=deployment.get("max_out_tokens"), dtype=dtype, param_dtype=dtype)
    return _seeded_model(dict(config.get("draw") or {}))(LlamaConfig(**{**sizes, **overrides}))


def scaled_draw(params, draw, served):
    """``params`` as the package draws them in FLOAT32 (N(0, 0.02) tables and
    kernels, ones for the norms), the leaves of the modules ``draw`` names
    (``embed_tokens``, ``input_layernorm_2``, ...: the module's name in the
    tree) multiplied by its number (powers of two: exact in bfloat16; a kind it
    leaves out stays as drawn), each leaf cast to the type it is ``served`` in.

    Why drawn in float32 and cast: ``jax.random.normal`` in bfloat16 has 128
    values and a mean of -0.012 of its spread. Every matrix drawn so carries the
    same rank-one part along the all-ones direction, with a gain of 0.48 at
    2,048 inputs beside the random part's 1.8 (0.06 beside 0.64 at 256: it grows
    with the width's root), the 384 sublayers of the looped stack add it up, and
    every position of every prompt decodes the one token whose column of the
    head sums highest. The configuration's ``assumed.weights`` has the readings
    and what each multiplier is for."""
    def drawn(path, w):
        names = [getattr(k, "key", None) for k in path]
        by = next((draw[name] for name in names if name in draw), 1)
        return (w * by).astype(served)
    return jax.tree_util.tree_map_with_path(drawn, params)


def _seeded_model(draw):
    """The package's model class with one thing changed: ``init`` returns the
    benchmark's seeded weights (:func:`scaled_draw` over the package's own
    draw made in float32, unboxed). The runner makes the weights by
    ``model.init`` inside one jitted call, where a leaf's draw, multiplier and
    cast are one fusion (the chip's compiler, described here: no temporary), so
    no float32 copy of the parameters exists; it gives the family no later hand
    on them."""
    import dataclasses

    import flax.linen as nn
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    if not draw:
        return LlamaForCausalLM

    class SeededOuro(LlamaForCausalLM):
        def init(self, *args, **kwargs):
            wide = LlamaForCausalLM(dataclasses.replace(self.config, param_dtype=jnp.float32),
                                    parent=None)
            variables = nn.meta.unbox(wide.init(*args, **kwargs))
            return {**variables, "params": scaled_draw(variables["params"], draw,
                                                       self.config.param_dtype)}

    return SeededOuro


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the decode tick's
    walk of a stored pool (``ops/pallas/pool_decode.py``, the call
    ``pool_decode``) is ``pallas:attn:decode``; any other custom call
    ``pallas:other`` (the looped stack's write, ``ops/pallas/pool_write.py``:
    ``PERF.md`` section 7 has the label and the share it waits for)."""
    name = trace.op_name(text).lstrip("%")
    if name.startswith("pool_decode"):
        return "pallas:attn:decode"
    if trace.is_custom_call(text):
        return "pallas:other"
    return trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict. Only views
    and reshapes: the leaves stay as and where they are served."""
    flat = {"embed": params["embed_tokens"], "norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"],
            "gate_w": params["exit_gate"]["kernel"][:, 0], "gate_b": params["exit_gate"]["bias"][0]}
    n_layer = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n_layer):
        blk, pre = params[f"layers_{i}"], f"layers.{i}."
        att = blk["self_attn"]
        e = att["q_proj"]["kernel"].shape[0]
        flat.update({pre + "ln1": blk["input_layernorm"]["weight"],
                     pre + "ln2": blk["input_layernorm_2"]["weight"],
                     pre + "ln3": blk["post_attention_layernorm"]["weight"],
                     pre + "ln4": blk["post_attention_layernorm_2"]["weight"],
                     # [E, H, D] -> [E, H D]: heads contiguous
                     pre + "wq": att["q_proj"]["kernel"].reshape(e, -1),
                     pre + "wk": att["k_proj"]["kernel"].reshape(e, -1),
                     pre + "wv": att["v_proj"]["kernel"].reshape(e, -1),
                     pre + "wo": att["o_proj"]["kernel"].reshape(-1, e)})
        flat.update({pre + name: blk["mlp"][name + "_proj"]["kernel"]
                     for name in ("gate", "up", "down")})
    return flat


_embed = jax.jit(ref.embed)
_attention = jax.jit(ref.attention, static_argnums=(2,))
_feed_forward = jax.jit(ref.feed_forward, static_argnums=(2,))
_close_pass = jax.jit(ref.close_pass, static_argnums=(2,))
_head = jax.jit(ref.head)

#: what :func:`model` last built: the heads, the passes, the norm's epsilon and
#: RoPE's base are given by no weight's shape, and the runner hands
#: :func:`reference_logits` the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head=None, sizes=None):
    """Reference logits [B, L, V] (a host array) for the configuration
    :func:`model` was last called with (or ``sizes``), a sequence at a time, a
    layer's half to a program, every pass anew, and the head a block of
    positions at a time."""
    sizes = sizes or _built["sizes"]
    top = {key: flat[key] for key in ("norm", "gate_w", "gate_b")}

    def one(row):
        h = _embed({"embed": flat["embed"]}, row[None])
        for _ in range(sizes.passes):
            x = h
            for i in range(ref.n_layers(flat)):
                bp = ref.block_params(flat, i)
                x = _feed_forward(bp, _attention(bp, x, sizes), sizes)
            h, _ = _close_pass(top, x, sizes)
        return np.concatenate([np.asarray(_head({"head": flat["head"]}, h[:, at:at + HEAD_BLOCK]),
                                          np.float32)
                               for at in range(0, h.shape[1], HEAD_BLOCK)], axis=1)[0]

    return np.stack([one(row) for row in np.asarray(ids)])
