"""The Laguna family: builds the package's model (a configuration of
``deepspeed_tpu/models/llama.py``: window layers over rings beside full
layers' pools, query heads by layer, a gate a head, RoPE by layer type with
YaRN on part of a head, a leading dense layer, sigmoid-routed experts held
whole with a shared one) from a configuration file whose ``family`` is
``laguna``, maps the package's parameter tree onto the reference's flat names,
and holds the two sides against each other.

The reference (``benchmarks/reference/laguna.py``) is run a sequence at a
time and a layer's half at a time through one jitted program each (attention;
a feed-forward, its experts one at a time), each weight upcast from the served
leaf as it is used, and the head over blocks of positions whose logits are
gathered on the host: a float32 copy of the weights (15.5 GB) does not fit the
chip, let alone beside the server, and neither do two sequences' logits over
100,352 rows.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import laguna as ref

#: positions whose logits the reference's head makes at a time
HEAD_BLOCK = 1024


def _rope(entry):
    """One layer type's entry of ``rope_parameters`` as the reference takes it."""
    plain = entry["rope_type"] == "default"
    return ref.Rope(
        theta=float(entry["rope_theta"]),
        partial_rotary_factor=float(entry.get("partial_rotary_factor", 1.0)),
        factor=None if plain else float(entry["factor"]),
        original_max_position_embeddings=int(entry.get("original_max_position_embeddings", 4096)),
        beta_fast=float(entry.get("beta_fast", 32)), beta_slow=float(entry.get("beta_slow", 1)),
        attention_factor=float(entry.get("attention_factor", 1.0)))


def _sizes(config):
    ropes = config["rope_parameters"]
    return ref.Sizes(layer_types=tuple(config["layer_types"]), head_dim=config["head_dim"],
                     window=config["sliding_window"], top_k=config["num_experts_per_tok"],
                     routed_scale=float(config["moe_routed_scaling_factor"]),
                     eps=float(config["rms_norm_eps"]),
                     rope_full=_rope(ropes["full_attention"]),
                     rope_sliding=_rope(ropes["sliding_attention"]))


def _rope_kind(entry):
    """The same entry as the package takes it (``llama.RopeKind``'s fields)."""
    kind = _rope(entry)
    return dict(theta=kind.theta, rotary_share=kind.partial_rotary_factor,
                yarn_factor=kind.factor, original_positions=kind.original_max_position_embeddings,
                beta_fast=kind.beta_fast, beta_slow=kind.beta_slow,
                attention_factor=kind.attention_factor if kind.factor is not None else None)


def window_ring(config, deployment):
    """Positions of a sliding layer's ring in this deployment: the window less
    one and a prefill chunk, in whole pages of the write's 128-position
    windows (``models/common.py`` ``window_ring_positions``)."""
    from deepspeed_tpu.models.common import window_ring_positions
    return window_ring_positions(config["sliding_window"], deployment["prefill_chunk"])


def model(config, deployment, **overrides):
    """The package's model at the sizes of ``config`` (the parsed configuration
    file, keys as published). ``deployment`` is the ``serve`` block: parameters
    are made in the type they are served in, a full layer's pools hold
    ``max_out_tokens`` positions a slot and a sliding layer's ring
    :func:`window_ring`; a decode attention walks its stored pool
    ``decode_key_block`` positions a step. ``draw`` holds the seeded draw's
    multipliers (:func:`scaled_draw`)."""
    from deepspeed_tpu.models.llama import LlamaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["sizes"] = _sizes(config)
    types, mlps = config["layer_types"], config["mlp_layer_types"]
    dense = mlps.index("sparse") if "sparse" in mlps else len(mlps)
    if any(kind != "sparse" for kind in mlps[dense:]) or len(types) != config["num_hidden_layers"]:
        raise NotImplementedError("mlp_layer_types: leading dense layers, then sparse ones")
    if config.get("moe_apply_router_weight_on_input"):
        raise NotImplementedError("moe_apply_router_weight_on_input is not built")
    same = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim", "max_position_embeddings",
            "rms_norm_eps", "attention_bias", "moe_intermediate_size")
    ropes = config["rope_parameters"]
    sizes = dict(
        {key: config[key] for key in same},
        num_attention_heads_layout=tuple(config["num_attention_heads_per_layer"]),
        sliding_window=config["sliding_window"],
        sliding_window_layout=tuple(int(kind == "sliding_attention") for kind in types),
        window_ring=window_ring(config, deployment),
        decode_key_block=deployment["decode_key_block"],
        attention_gate="headwise" if config["gating"] else None,
        rope_full=_rope_kind(ropes["full_attention"]),
        rope_window=_rope_kind(ropes["sliding_attention"]),
        moe_num_experts=config["num_experts"], moe_k=config["num_experts_per_tok"],
        moe_first_dense=dense, moe_shared_intermediate_size=config["shared_expert_intermediate_size"],
        moe_score="sigmoid", moe_routed_scale=float(config["moe_routed_scaling_factor"]),
        moe_norm_topk_prob=True, moe_drop_tokens=False, moe_aux_loss_coef=0.0,
        # every expert is held: the layer that holds a share counts its rows and
        # the experts they touch on the device, and routes no padding
        moe_experts_held=(0, config["num_experts"]),
        # the head over 100,352 rows for the one position a slot a prefill tick keeps
        head_last_fed_only=True,
        decode_cache_len=deployment.get("max_out_tokens"), dtype=dtype, param_dtype=dtype)
    return _seeded_model(dict(config.get("draw", {})))(LlamaConfig(**{**sizes, **overrides}))


def scaled_draw(params, draw):
    """The package's plain N(0, 0.02) draw with the kinds of leaf the
    configuration's ``draw`` names multiplied by it (powers of two: exact in
    bfloat16; a kind it leaves out stays as drawn). ``routed_down_proj``: every
    routed expert's down projection, as ``families/joyai_llm_flash.py`` draws
    them and for its reason (the configuration's ``assumed.weights``)."""
    def scale(path, w):
        names = [getattr(k, "key", None) for k in path]
        if names[-4:] == ["experts", "deepspeed_experts", "down_proj", "kernel"]:
            return (w * draw.get("routed_down_proj", 1)).astype(w.dtype)
        return w
    return jax.tree_util.tree_map_with_path(scale, params)


def _seeded_model(draw):
    """The package's model class with one thing changed: ``init`` returns the
    benchmark's seeded weights (:func:`scaled_draw` over the package's own
    draw, unboxed). The runner makes the weights by ``model.init`` and gives
    the family no later hand on them."""
    import flax.linen as nn
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    class SeededLaguna(LlamaForCausalLM):
        def init(self, *args, **kwargs):
            variables = nn.meta.unbox(super().init(*args, **kwargs))
            return {**variables, "params": scaled_draw(variables["params"], draw)}

    return SeededLaguna


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the grouped expert
    matmuls (``%gmm``, or XLA's ``%ragged-dot``) are ``pallas:moe:matmul``, any
    other custom call ``pallas:other`` (none runs in the cell as configured).
    The attention runs as XLA loops and fusions (``llama.cached_attention``,
    under the scopes ``attn_full`` and ``attn_window``) and has no name of its
    own in the trace: the loader keeps no operation metadata (PERF.md section
    7 has the edit that would let a reader see the scopes)."""
    name = trace.op_name(text).lstrip("%")
    if name.startswith(("gmm", "ragged-dot")):
        return "pallas:moe:matmul"
    if trace.is_custom_call(text):
        return "pallas:other"
    return trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict. Only views
    and reshapes: the leaves stay as and where they are served."""
    flat = {"embed": params["embed_tokens"], "norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"]}
    n_layer = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n_layer):
        blk, pre = params[f"layers_{i}"], f"layers.{i}."
        att = blk["self_attn"]
        e = att["q_proj"]["kernel"].shape[0]
        flat.update({pre + "ln_attn": blk["input_layernorm"]["weight"],
                     pre + "ln_ffn": blk["post_attention_layernorm"]["weight"],
                     # [E, H, D] -> [E, H D]: heads contiguous
                     pre + "wq": att["q_proj"]["kernel"].reshape(e, -1),
                     pre + "wk": att["k_proj"]["kernel"].reshape(e, -1),
                     pre + "wv": att["v_proj"]["kernel"].reshape(e, -1),
                     pre + "wg": att["gate_proj"]["kernel"],
                     pre + "wo": att["o_proj"]["kernel"].reshape(-1, e)})
        if "mlp" in blk:
            flat.update({pre + name: blk["mlp"][name + "_proj"]["kernel"]
                         for name in ("gate", "up", "down")})
            continue
        moe = blk["moe"]["deepspeed_moe"]
        bank, shared = moe["experts"]["deepspeed_experts"], moe["shared_expert"]
        flat[pre + "router"] = moe["gate"]["wg"]
        for name in ("gate", "up", "down"):
            flat[pre + "w_" + name] = bank[name + "_proj"]["kernel"]
            flat[pre + "shared_" + name] = shared[name + "_proj"]["kernel"]
    return flat


_embed = jax.jit(ref.embed)
_attention = jax.jit(ref.attention, static_argnums=(2, 3))
_feed_forward = jax.jit(ref.feed_forward, static_argnums=(2,))
_head = jax.jit(ref.head, static_argnums=(2,))

#: what :func:`model` last built: the kinds of layer, the window, the routing
#: sizes and the RoPE settings are given by no weight's shape (the heads by
#: layer are: ``wq``'s columns), and the runner hands :func:`reference_logits`
#: the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head=None, sizes=None):
    """Reference logits [B, L, V] (a host array) for the configuration
    :func:`model` was last called with (or ``sizes``), a sequence at a time, a
    layer's half to a program and the head a block of positions at a time."""
    sizes = sizes or _built["sizes"]
    top = {"norm": flat["norm"], "head": flat["head"]}

    def one(row):
        x = _embed({"embed": flat["embed"]}, row[None])
        for i in range(sizes.n_layer):
            bp = ref.block_params(flat, i)
            x = _feed_forward(bp, _attention(bp, x, sizes, i), sizes)
        return np.concatenate([np.asarray(_head(top, x[:, at:at + HEAD_BLOCK], sizes), np.float32)
                               for at in range(0, x.shape[1], HEAD_BLOCK)], axis=1)[0]

    return np.stack([one(row) for row in np.asarray(ids)])
