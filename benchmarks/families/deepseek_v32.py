"""The DeepSeek-V3.2 family: builds the package's model
(``deepspeed_tpu/models/deepseek_v3.py``: every layer indexed, YaRN,
group-limited routing) from a configuration file whose ``family`` is
``deepseek_v32``, maps the package's parameter tree onto the reference's flat
names, and holds the two sides against each other.

The reference (``benchmarks/reference/deepseek_v32.py``) is run a sequence at
a time and a layer's half at a time through one jitted program each
(attention, index scores and the sort over blocks of query rows; the dense
layer and the shared expert over blocks of positions; an expert layer one
expert at a time), each weight upcast from the served leaf as it is used, and
the head over blocks of positions whose logits are gathered on the host: a
float32 copy of the weights (15.3 GB) does not fit the chip, let alone beside
the server's 12.1.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import deepseek_v32 as ref

#: positions whose logits the reference's head makes at a time, and whose
#: feed-forward layer runs at a time (18,432-wide float32 activations of 6,128
#: positions are 0.45 GB each of three)
HEAD_BLOCK = 1024


def _yarn(scaling):
    if scaling is None:
        return None
    if scaling.get("type", "yarn") != "yarn":
        raise NotImplementedError(f"rope_scaling of type {scaling['type']!r}")
    return ref.Yarn(float(scaling["factor"]), int(scaling["original_max_position_embeddings"]),
                    float(scaling["beta_fast"]), float(scaling["beta_slow"]),
                    float(scaling["mscale"]), float(scaling["mscale_all_dim"]))


def _sizes(config):
    held = config.get("experts_held") or [0, config["n_routed_experts"]]
    return ref.Sizes(n_layer=config["num_hidden_layers"], n_dense=config["first_k_dense_replace"],
                     d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
                     rank=config["kv_lora_rank"], theta=float(config["rope_theta"]),
                     index_top_k=config["index_topk"], top_k=config["num_experts_per_tok"],
                     n_group=config["n_group"], topk_group=config["topk_group"],
                     routed_scale=float(config["routed_scaling_factor"]),
                     yarn=_yarn(config.get("rope_scaling")), experts_first=int(held[0]),
                     eps=float(config["rms_norm_eps"]), index_eps=float(config["rms_norm_eps"]))


def model(config, deployment, **overrides):
    """The package's model at the sizes of ``config`` (the parsed
    configuration file, keys as published). ``n_routed_experts`` is how many
    experts are *held* (``experts_held`` = [first, count] says which); the
    router keeps ``n_routed_experts_published`` outputs in ``n_group`` groups.
    ``deployment`` is the ``serve`` block: parameters are made in the type
    they are served in, and every layer's latent pool and index-key pool hold
    ``max_out_tokens`` positions a slot. ``draw`` holds the seeded draw's
    multipliers (:func:`scaled_draw`)."""
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["sizes"] = _sizes(config)
    held = config.get("experts_held")
    same = ("vocab_size", "hidden_size", "num_hidden_layers", "rms_norm_eps",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "max_position_embeddings", "first_k_dense_replace",
            "intermediate_size", "num_experts_per_tok", "moe_intermediate_size",
            "n_shared_experts", "norm_topk_prob", "n_group", "topk_group", "index_topk",
            "index_n_heads", "index_head_dim")
    sizes = dict(
        {key: config[key] for key in same},
        rope_theta=float(config["rope_theta"]), rope_scaling=config.get("rope_scaling"),
        # the release's indexer turns its rotated dimensions half-split
        index_rope_interleave=False,
        decode_cache_len=deployment.get("max_out_tokens"),
        n_routed_experts=config.get("n_routed_experts_published", config["n_routed_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        experts_held=tuple(held) if held else None, dtype=dtype, param_dtype=dtype)
    return _seeded_model(dict(config.get("draw") or {}))(DeepseekV3Config(**{**sizes, **overrides}))


def scaled_draw(params, draw):
    """The package's plain N(0, 0.02) draw with the kinds of leaf the
    configuration's ``draw`` names multiplied by its numbers (powers of two:
    exact in bfloat16; a kind it leaves out stays as drawn), each for what the
    chip's check read without it (``assumed.weights`` has the readings):
    ``routed_down_proj`` (every routed expert's down projection) and
    ``embed_tokens`` (the token table), as ``families/dots3_note.py`` draws
    them and for its reasons. The router's selection bias is the package's own
    draw, N(0, 0.02) and not the release's zero, so that the bias is no no-op."""
    def scale(path, w):
        names = [getattr(k, "key", None) for k in path]
        if names[-4:] == ["experts", "deepspeed_experts", "down_proj", "kernel"]:
            by = draw.get("routed_down_proj", 1)
        elif names == ["embed_tokens"]:
            by = draw.get("embed_tokens", 1)
        else:
            return w
        return (w * by).astype(w.dtype)
    return jax.tree_util.tree_map_with_path(scale, params)


def _seeded_model(draw):
    """The package's model class with one thing changed: ``init`` returns the
    benchmark's seeded weights (:func:`scaled_draw` over the package's own
    draw, unboxed). The runner makes the weights by ``model.init`` and gives
    the family no later hand on them."""
    import flax.linen as nn
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM

    class SeededDeepseekV32(DeepseekV3ForCausalLM):
        def init(self, *args, **kwargs):
            variables = nn.meta.unbox(super().init(*args, **kwargs))
            return {**variables, "params": scaled_draw(variables["params"], draw)}

    return SeededDeepseekV32


#: a custom call's name, as XLA derives it from the kernel's, to its label
KERNELS = (("dsa_index_decode", "pallas:dsa:index_decode"),
           ("dsa_index_prefill", "pallas:dsa:index_prefill"),
           ("dsa_prefill_walk", "pallas:dsa:prefill_walk"),
           ("dsa_select", "pallas:dsa:select"),
           ("dsa_decode", "pallas:dsa:decode"))


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the grouped
    expert matmuls (``%gmm``, or XLA's ``%ragged-dot``) are
    ``pallas:moe:matmul``; a custom call named after one of :data:`KERNELS`
    (``ops/pallas/sparse_index.py``'s two, ``ops/pallas/sparse_select.py``,
    ``ops/pallas/latent_walk.py``, ``ops/pallas/latent_decode.py`` over a
    selection) takes its label."""
    name = trace.op_name(text).lstrip("%")
    if name.startswith(("gmm", "ragged-dot")):
        return "pallas:moe:matmul"
    if trace.is_custom_call(text):
        for prefix, label in KERNELS:
            if name.startswith(prefix):
                return label
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
                     pre + "kv_b": att["kv_b_proj"], pre + "wo": att["o_proj"]["kernel"],
                     pre + "idx_q": att["indexer_q_proj"]["kernel"],
                     pre + "idx_k": att["indexer_k_proj"]["kernel"],
                     pre + "idx_k_norm": att["indexer_k_norm"]["scale"],
                     pre + "idx_k_norm_bias": att["indexer_k_norm"]["bias"],
                     pre + "idx_w": att["indexer_weights_proj"]["kernel"]})
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

#: what :func:`model` last built: the head sizes, the routing sizes, theta and
#: YaRN's numbers are given by no weight's shape, and the runner hands
#: :func:`reference_logits` the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head=None, sizes=None):
    """Reference logits [B, L, V] (a host array) for the configuration
    :func:`model` was last called with (or ``sizes``): a sequence at a time, a
    layer's half to a program, the feed-forward half and the head a block of
    positions at a time (every position's feed-forward result is its own)."""
    sizes = sizes or _built["sizes"]
    top = {"norm": flat["norm"], "head": flat["head"]}

    def blocks(fn, x):
        return jnp.concatenate([fn(x[:, at:at + HEAD_BLOCK])
                                for at in range(0, x.shape[1], HEAD_BLOCK)], axis=1)

    def one(row):
        x = _embed({"embed": flat["embed"]}, row[None])
        for i in range(sizes.n_layer):
            bp = ref.block_params(flat, i)
            x = _attention(bp, x, sizes)
            x = blocks(lambda piece: _feed_forward(bp, piece, sizes), x)
        return np.concatenate([np.asarray(_head(top, x[:, at:at + HEAD_BLOCK], sizes), np.float32)
                               for at in range(0, x.shape[1], HEAD_BLOCK)], axis=1)[0]

    # a sequence at a time: 128 heads' float32 queries, keys and values of two
    # 6,128-position sequences (6 GB) do not fit beside the server's 12.1
    return np.stack([one(row) for row in np.asarray(ids)])
