"""The JoyAI-LLM-Flash family: builds the package's model
(``deepspeed_tpu/models/deepseek_v3.py``) from a configuration file whose
``family`` is ``joyai_llm_flash``, maps the package's parameter tree onto the
reference's flat names, and holds the two sides against each other.

The reference (``benchmarks/reference/joyai_llm_flash.py``) is run a layer's
half at a time through one jitted program each (attention over blocks of
query rows, an expert layer one expert at a time), each weight upcast from
the served leaf as it is used, and the head over blocks of positions whose
logits are gathered on the host: a float32 copy of the weights (12.8 GB) or
of two 6,128-position sequences' logits (1.6 GB) does not fit beside the
server.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import joyai_llm_flash as ref

#: positions whose logits the reference's head makes at a time
HEAD_BLOCK = 1024


def _sizes(config):
    held = config.get("experts_held") or [0, config["n_routed_experts"]]
    return ref.Sizes(n_layer=config["num_hidden_layers"], n_dense=config["first_k_dense_replace"],
                     d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
                     rank=config["kv_lora_rank"], top_k=config["num_experts_per_tok"],
                     routed_scale=float(config["routed_scaling_factor"]),
                     rope_theta=float(config["rope_theta"]), experts_first=int(held[0]),
                     eps=float(config["rms_norm_eps"]))


def model(config, deployment, **overrides):
    """The package's model at the sizes of ``config`` (the parsed
    configuration file, keys as published). ``n_routed_experts`` is how many
    experts are *held* (``experts_held`` = [first, count] says which); the
    router keeps ``n_routed_experts_published`` outputs. ``deployment`` is
    the ``serve`` block: parameters are made in the type they are served in,
    and the latent pool holds ``max_out_tokens`` positions a slot."""
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["sizes"] = _sizes(config)
    held = config.get("experts_held")
    sizes = dict(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"], rms_norm_eps=config["rms_norm_eps"],
        num_attention_heads=config["num_attention_heads"], q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"], qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        max_position_embeddings=config["max_position_embeddings"],
        decode_cache_len=deployment.get("max_out_tokens"),
        first_k_dense_replace=config["first_k_dense_replace"],
        intermediate_size=config["intermediate_size"],
        n_routed_experts=config.get("n_routed_experts_published", config["n_routed_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=config["norm_topk_prob"], n_group=config["n_group"],
        topk_group=config["topk_group"], experts_held=tuple(held) if held else None,
        dtype=dtype, param_dtype=dtype)
    if config.get("rope_scaling") is not None or not config.get("rope_interleave", True):
        raise NotImplementedError("rope_scaling and half-split RoPE are not built for this family")
    return _seeded_model()(DeepseekV3Config(**{**sizes, **overrides}))


#: the configuration's ``assumed.weights``: a routed expert's down projection is
#: drawn N(0, 0.02 x this), every other matrix N(0, 0.02)
ROUTED_DOWN_SCALE = 0.25


def scale_routed_down_projections(params):
    """The package's plain N(0, 0.02) draw with the routed experts' down
    projections a quarter of it. Drawn alike, one routed expert of a token's
    eight (weight 2.5 / 8) is 8% of the stream it adds to in these ten
    layers, and the 8th and 9th of 256 sigmoid scores of a random linear
    router lie 0.056 of their spread apart, whatever the router's scale: the
    rounding of a bfloat16 operand swaps them for one token in ten, each swap
    is as large as what fp8 weights do to the whole stream, and it swaps the
    layers after it. A network's own experts each move a stream tens of
    layers deep by a percent or two; a quarter of the draw is 2% here
    (``PERF.md`` section 6, PR 32, has the readings either way). The package's
    initialiser stays the family's plain one."""
    def scale(path, w):
        names = [getattr(k, "key", None) for k in path]
        if names[-4:] != ["experts", "deepspeed_experts", "down_proj", "kernel"]:
            return w
        return (w * ROUTED_DOWN_SCALE).astype(w.dtype)       # a power of two: exact
    return jax.tree_util.tree_map_with_path(scale, params)


def _seeded_model():
    """The package's model class with one thing changed: ``init`` returns the
    benchmark's seeded weights (:func:`scale_routed_down_projections` over the
    package's own draw, unboxed). The runner makes the weights by
    ``model.init`` and gives the family no later hand on them."""
    import flax.linen as nn
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM

    class SeededDeepseekV3(DeepseekV3ForCausalLM):
        def init(self, *args, **kwargs):
            variables = nn.meta.unbox(super().init(*args, **kwargs))
            return {**variables, "params": scale_routed_down_projections(variables["params"])}

    return SeededDeepseekV3


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the grouped
    expert matmuls (``%gmm``, or XLA's ``%ragged-dot``) are
    ``pallas:moe:matmul``; a custom call named after a latent-attention
    kernel is ``pallas:mla:decode`` (``mla_decode``, the absorbed step:
    ``ops/pallas/latent_decode.py``) or ``pallas:mla:prefill``
    (``mla_prefill_walk``, a chunk's expanded walk, one call a fed slot a
    layer: ``ops/pallas/latent_walk.py::causal_walk``)."""
    name = trace.op_name(text).lstrip("%")
    if name.startswith(("gmm", "ragged-dot")):
        return "pallas:moe:matmul"
    if trace.is_custom_call(text):
        for kind in ("prefill", "decode"):
            if name.startswith("mla_" + kind):
                return "pallas:mla:" + kind
        return "pallas:other"
    return trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict. Only
    views: the leaves stay as and where they are served."""
    flat = {"embed": params["embed_tokens"], "norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"]}
    n_layer = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n_layer):
        blk, pre = params[f"layers_{i}"], f"layers.{i}."
        att, mlp = blk["self_attn"], blk["mlp"]
        flat.update({pre + "ln1": blk["input_layernorm"]["weight"],
                     pre + "ln2": blk["post_attention_layernorm"]["weight"],
                     pre + "q_a": att["q_a_proj"]["kernel"],
                     pre + "q_a_norm": att["q_a_layernorm"]["weight"],
                     pre + "q_b": att["q_b_proj"]["kernel"],
                     pre + "kv_a": att["kv_a_proj_with_mqa"]["kernel"],
                     pre + "kv_a_norm": att["kv_a_layernorm"]["weight"],
                     pre + "kv_b": att["kv_b_proj"], pre + "wo": att["o_proj"]["kernel"]})
        if "gate" not in mlp:
            flat.update({pre + name: mlp[name + "_proj"]["kernel"]
                         for name in ("gate", "up", "down")})
            continue
        bank, shared = mlp["experts"]["deepspeed_experts"], mlp["shared_expert"]
        flat.update({pre + "router": mlp["gate"]["wg"],
                     pre + "router_bias": mlp["gate"]["e_score_correction_bias"]})
        for name in ("gate", "up", "down"):
            flat[pre + "w_" + name] = bank[name + "_proj"]["kernel"]
            flat[pre + "shared_" + name] = shared[name + "_proj"]["kernel"]
    return flat


_embed = jax.jit(ref.embed)
_attention = jax.jit(ref.attention, static_argnums=(2,))
_feed_forward = jax.jit(ref.feed_forward, static_argnums=(2,))
_head = jax.jit(ref.head, static_argnums=(2,))

#: what :func:`model` last built: the head sizes, the routing sizes and theta
#: are given by no weight's shape, and the runner hands
#: :func:`reference_logits` the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head=None, sizes=None):
    """Reference logits [B, L, V] (a host array) for the configuration
    :func:`model` was last called with (or ``sizes``), a layer's half to a
    program and the head a block of positions at a time."""
    sizes = sizes or _built["sizes"]
    x = _embed({"embed": flat["embed"]}, ids)
    for i in range(sizes.n_layer):
        bp = ref.block_params(flat, i)
        x = _feed_forward(bp, _attention(bp, x, sizes), sizes)
    top = {"norm": flat["norm"], "head": flat["head"]}
    return np.concatenate([np.asarray(_head(top, x[:, at:at + HEAD_BLOCK], sizes), np.float32)
                           for at in range(0, x.shape[1], HEAD_BLOCK)], axis=1)
