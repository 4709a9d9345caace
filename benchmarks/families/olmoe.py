"""The OLMoE family: builds the package's model (a configuration of
``models/llama.py``) from a configuration file whose ``family`` is
``olmoe``, maps the package's parameter tree onto the reference's flat
names, and holds the two sides against each other.

The reference (``benchmarks/reference/olmoe.py``) is run a block at a time
through one jitted program per piece, and inside a block one expert at a
time, each upcast from the served weights as it is used: a float32 copy of
all the weights (14 GB at 8 layers) does not fit beside the server.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import trace
from benchmarks.reference import olmoe as ref


def model(config, deployment, **overrides):
    """The package's OLMoE at the sizes of ``config`` (the parsed
    configuration file, keys as published). ``deployment`` is its ``serve``
    block: parameters are made in the type they are served in, so that no
    float32 copy of the whole tree ever exists, and each slot's cache holds
    ``max_out_tokens`` positions (the context stays the published one)."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["top_k"] = int(config["num_experts_per_tok"])
    sizes = dict(vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
                 intermediate_size=config["intermediate_size"],
                 num_hidden_layers=config["num_hidden_layers"],
                 num_attention_heads=config["num_attention_heads"],
                 num_key_value_heads=config["num_key_value_heads"],
                 max_position_embeddings=config["max_position_embeddings"],
                 rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
                 attention_bias=config["attention_bias"], qk_norm=True,
                 moe_num_experts=config["num_experts"], moe_k=config["num_experts_per_tok"],
                 moe_norm_topk_prob=config["norm_topk_prob"], moe_drop_tokens=False,
                 moe_layer_freq=1, decode_cache_len=deployment.get("max_out_tokens"),
                 dtype=dtype, param_dtype=dtype)
    return LlamaForCausalLM(LlamaConfig(**{**sizes, **overrides}))


def op_label(text, stats=None):
    """Names this family's kernels in a device trace, from the instruction
    names XLA derives from the program's scopes (the events carry no other
    metadata): the grouped expert matmuls (``%gmm``; ``%ragged-dot`` is what
    XLA's own kernels would be called, and no option of the program chooses
    them) and the row
    permutations under the ``moe_route`` / ``moe_combine`` scopes. Any other
    Mosaic custom call of a llama-family serving program is an attention
    kernel (``use_flash_prefill``; none runs in the cell as configured)."""
    name = trace.op_name(text).lstrip("%")
    if name.startswith(("gmm", "ragged-dot")):
        return "pallas:moe:matmul"
    if trace.is_custom_call(text):
        return "pallas:moe:permute" if name.startswith("moe_") else "pallas:attn"
    return trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict. Only
    views and reshapes: the leaves stay as and where they are served."""
    flat = {"embed": params["embed_tokens"], "norm": params["norm"]["weight"],
            "head": params["lm_head"]["kernel"]}
    n_layer = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n_layer):
        blk, pre = params[f"layers_{i}"], f"layers.{i}."
        att, moe = blk["self_attn"], blk["moe"]["deepspeed_moe"]
        bank = moe["experts"]["deepspeed_experts"]
        e = att["q_proj"]["kernel"].shape[0]
        flat.update({
            pre + "ln_attn": blk["input_layernorm"]["weight"],
            pre + "ln_ffn": blk["post_attention_layernorm"]["weight"],
            # [E, H, D] -> [E, E]: heads contiguous
            pre + "wq": att["q_proj"]["kernel"].reshape(e, -1),
            pre + "wk": att["k_proj"]["kernel"].reshape(e, -1),
            pre + "wv": att["v_proj"]["kernel"].reshape(e, -1),
            pre + "wo": att["o_proj"]["kernel"].reshape(-1, e),
            pre + "q_norm": att["q_norm"]["weight"], pre + "k_norm": att["k_norm"]["weight"],
            pre + "router": moe["gate"]["wg"],
            pre + "gate": bank["gate_proj"]["kernel"], pre + "up": bank["up_proj"]["kernel"],
            pre + "down": bank["down_proj"]["kernel"],
        })
    return flat


_embed = jax.jit(ref.embed)
_block = jax.jit(ref.block, static_argnums=(2, 3))
_head = jax.jit(ref.head)


#: what :func:`model` last built: the number of experts a token takes is
#: the one size of the routing that no weight's shape gives, and the runner
#: hands :func:`reference_logits` the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head):
    """Reference logits [B, L, V], a block to a program, for the
    configuration :func:`model` was last called with."""
    top = {k: flat[k] for k in ("embed", "norm", "head")}
    x = _embed(top, ids)
    for i in range(ref.n_layers(flat)):
        x = _block(ref.block_params(flat, i), x, n_head, _built["top_k"])
    return _head(top, x)
