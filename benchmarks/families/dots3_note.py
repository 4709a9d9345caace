"""The dots3-note family: builds the package's model
(``deepspeed_tpu/models/deepseek_v3.py``, layers of two kinds) from a
configuration file whose ``family`` is ``dots3_note``, maps the package's
parameter tree onto the reference's flat names, and holds the two sides
against each other.

The reference (``benchmarks/reference/dots3_note.py``) is run a layer's half
at a time through one jitted program each (attention, index scores and the
sort over blocks of query rows, an expert layer one expert at a time), each
weight upcast from the served leaf as it is used, and the head over blocks of
positions whose logits are gathered on the host: a float32 copy of the
weights (16.3 GB) does not fit the chip, let alone beside the server.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import dots3_note as ref

#: positions whose logits the reference's head makes at a time
HEAD_BLOCK = 1024
#: key positions a step of XLA's expanded walk takes (the window layers' walk
#: over their ring; an indexed layer's runs as a kernel on the chip): it
#: divides a ring of 768, which the package's 512 does not, and at 128 heads
#: the chip read a full layer's walk 146 ms at 256 for 194 at 512 and 156 at
#: 128 (PERF.md section 6, PR 37)
WALK_KEY_BLOCK = 256


def _kind(config, layer):
    if config["layer_types"][layer] == "sliding_attention":
        return ref.Kind(config["swa_qk_nope_head_dim"], config["swa_qk_rope_head_dim"],
                        config["swa_kv_lora_rank"], config["swa_q_lora_rank"],
                        float(config["swa_rope_theta"]), window=config["sliding_window_size"])
    return ref.Kind(config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                    config["kv_lora_rank"], config["q_lora_rank"], float(config["rope_theta"]),
                    top_k=config["index_topk"])


def _sizes(config):
    held = config.get("experts_held") or [0, config["n_routed_experts"]]
    return ref.Sizes(kinds=tuple(_kind(config, i) for i in range(config["num_hidden_layers"])),
                     n_dense=config["first_k_dense_replace"], hidden=config["hidden_size"],
                     top_k=config["num_experts_per_tok"],
                     routed_scale=float(config["routed_scaling_factor"]),
                     rescale=bool(config["apply_mla_qkv_lora_rescale"]),
                     experts_first=int(held[0]), eps=float(config["rms_norm_eps"]))


def window_ring(config, deployment):
    """Positions of a sliding layer's ring in this deployment: the window less
    one and a prefill chunk, in whole pages of the write's 128-position
    windows (``deepseek_v3.window_ring_positions``)."""
    from deepspeed_tpu.models.deepseek_v3 import window_ring_positions
    return window_ring_positions(config["sliding_window_size"], deployment["prefill_chunk"])


def model(config, deployment, **overrides):
    """The package's model at the sizes of ``config`` (the parsed
    configuration file, keys as published). ``n_routed_experts`` is how many
    experts are *held* (``experts_held`` = [first, count] says which); the
    router keeps ``n_routed_experts_published`` outputs. ``deployment`` is
    the ``serve`` block: parameters are made in the type they are served in,
    a full layer's pools hold ``max_out_tokens`` positions a slot and a
    sliding layer's ring :func:`window_ring`. ``draw`` holds the seeded
    draw's multipliers (:func:`scaled_draw`)."""
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["sizes"] = _sizes(config)
    held = config.get("experts_held")
    same = ("vocab_size", "hidden_size", "num_hidden_layers", "rms_norm_eps",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "max_position_embeddings", "first_k_dense_replace",
            "intermediate_size", "num_experts_per_tok", "moe_intermediate_size",
            "n_shared_experts", "norm_topk_prob", "swa_num_attention_heads", "swa_q_lora_rank",
            "swa_kv_lora_rank", "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
            "sliding_window_size", "index_topk", "index_n_heads", "index_head_dim")
    sizes = dict(
        {key: config[key] for key in same},
        rope_theta=float(config["rope_theta"]), swa_rope_theta=float(config["swa_rope_theta"]),
        layer_types=tuple(config["layer_types"]),
        decode_cache_len=deployment.get("max_out_tokens"),
        window_ring=window_ring(config, deployment),
        attention_key_block=WALK_KEY_BLOCK,
        n_routed_experts=config.get("n_routed_experts_published", config["n_routed_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        experts_held=tuple(held) if held else None,
        mla_lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
        attention_gate=config["attention_gate_type"],
        swa_attention_gate=config["swa_attention_gate_type"],
        dtype=dtype, param_dtype=dtype)
    if config.get("rope_scaling") is not None:
        raise NotImplementedError("rope_scaling is not built for this family")
    return _seeded_model(dict(config["draw"]))(DeepseekV3Config(**{**sizes, **overrides}))


def scaled_draw(params, draw):
    """The package's plain N(0, 0.02) draw with two kinds of leaf multiplied by
    the configuration's ``draw`` (powers of two: exact in bfloat16; a kind it
    leaves out stays as drawn), each for what the chip's check read without it
    (``assumed.weights`` has the readings):

    * ``routed_down_proj``, every routed expert's down projection (a quarter):
      as ``families/joyai_llm_flash.py`` draws them and for its reason
      (``PERF.md`` section 6, PR 32): drawn alike, the eighth and ninth of 256
      sigmoid scores swap under bfloat16 rounding for one token in ten and one
      swap moves the stream as fp8 weights do.
    * ``embed_tokens``, the token table (256 at the published sizes): a row of
      the plain draw is 0.02 an element and the first layer's attention output
      0.33, so the attention IS the stream the layers after it read, and the few
      positions whose index score lies within bfloat16 rounding of the 2,048th
      (seeded values are independent: trading 6 of 2,048 moves a head's output
      by a tenth, not by 6 / 2,048) moved the logits as fp8 weights do. A wider
      row leaves each attention a sixteenth of the stream, which is what a
      swap, and a wrong selection, then move.
    """
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

    class SeededDots3Note(DeepseekV3ForCausalLM):
        def init(self, *args, **kwargs):
            variables = nn.meta.unbox(super().init(*args, **kwargs))
            return {**variables, "params": scaled_draw(variables["params"], draw)}

    return SeededDots3Note


#: a custom call's name, as XLA derives it from the kernel's, to its label
KERNELS = (("dsa_index_decode", "pallas:dsa:index_decode"),
           ("dsa_index_prefill", "pallas:dsa:index_prefill"),
           ("dsa_prefill_walk", "pallas:dsa:prefill_walk"),
           ("dsa_decode", "pallas:dsa:decode"), ("mla_decode", "pallas:mla:decode"))


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the grouped
    expert matmuls (``%gmm``, or XLA's ``%ragged-dot``) are
    ``pallas:moe:matmul``; a custom call named after one of :data:`KERNELS`
    (``ops/pallas/sparse_index.py``'s two, ``ops/pallas/latent_walk.py``,
    ``ops/pallas/latent_decode.py`` over a selection) takes its label. The
    window layers' attention and the selection run as XLA loops and fusions
    and have no name of their own (PERF.md section 6, PR 37)."""
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
                     pre + "kv_b": att["kv_b_proj"], pre + "gate_h": att["gate_proj"]["kernel"],
                     pre + "wo": att["o_proj"]["kernel"]})
        if "indexer_q_proj" in att:
            flat.update({pre + "idx_q": att["indexer_q_proj"]["kernel"],
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
_attention = jax.jit(ref.attention, static_argnums=(2, 3))
_feed_forward = jax.jit(ref.feed_forward, static_argnums=(2,))
_head = jax.jit(ref.head, static_argnums=(2,))

#: what :func:`model` last built: the kinds of layer, the routing sizes and
#: the thetas are given by no weight's shape, and the runner hands
#: :func:`reference_logits` the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head=None, sizes=None):
    """Reference logits [B, L, V] (a host array) for the configuration
    :func:`model` was last called with (or ``sizes``), a layer's half to a
    program and the head a block of positions at a time."""
    sizes = sizes or _built["sizes"]
    top = {"norm": flat["norm"], "head": flat["head"]}

    def one(row):
        x = _embed({"embed": flat["embed"]}, row[None])
        for i in range(sizes.n_layer):
            bp = ref.block_params(flat, i)
            x = _feed_forward(bp, _attention(bp, x, sizes, i), sizes)
        return np.concatenate([np.asarray(_head(top, x[:, at:at + HEAD_BLOCK], sizes), np.float32)
                               for at in range(0, x.shape[1], HEAD_BLOCK)], axis=1)[0]

    # a sequence at a time: 128 heads' float32 queries, keys and values of two
    # 6,128-position sequences (6 GB) do not fit beside the server's 11.3
    return np.stack([one(row) for row in np.asarray(ids)])
