"""The Nemotron-H family: builds the package's model
(``deepspeed_tpu/models/nemotron_h.py``) from a configuration file whose
``family`` is ``nemotron_h``, maps the package's parameter tree onto the
reference's flat names, and holds the two sides against each other.

The reference (``benchmarks/reference/nemotron_h.py``) is run a layer at a
time through one jitted program per kind of layer, and inside an expert
layer one expert at a time, each upcast from the served weights as it is
used: a float32 copy of all the weights (18.6 GB) does not fit beside the
server.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import trace
from benchmarks.reference import nemotron_h as ref


def _sizes(config):
    held = config.get("experts_held") or [0, config["n_routed_experts"]]
    return ref.Sizes(pattern=config["hybrid_override_pattern"],
                     n_head=config["num_attention_heads"],
                     n_kv_head=config["num_key_value_heads"],
                     mamba_head_dim=config["mamba_head_dim"], n_groups=config["n_groups"],
                     top_k=config["num_experts_per_tok"],
                     routed_scale=float(config["routed_scaling_factor"]),
                     experts_first=int(held[0]))


def model(config, deployment, **overrides):
    """The package's model at the sizes of ``config`` (the parsed
    configuration file, keys as published). ``n_routed_experts`` is how many
    experts are *held* (``experts_held`` = [first, count] says which); the
    router keeps ``n_routed_experts_published`` outputs. ``deployment`` is
    the ``serve`` block: parameters are made in the type they are served in,
    and the attention layers' cache holds ``max_out_tokens`` positions."""
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[deployment["dtype"]]
    _built["sizes"] = _sizes(config)
    held = config.get("experts_held")
    sizes = dict(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        hybrid_override_pattern=config["hybrid_override_pattern"],
        layer_norm_epsilon=config["layer_norm_epsilon"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        max_position_embeddings=config["max_position_embeddings"],
        decode_cache_len=deployment.get("max_out_tokens"),
        mamba_num_heads=config["mamba_num_heads"], mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"], ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        time_step_min=config["time_step_min"], time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        n_routed_experts=config.get("n_routed_experts_published", config["n_routed_experts"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_latent_size=config["moe_latent_size"],
        moe_shared_expert_intermediate_size=config["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=tuple(held) if held else None,
        intermediate_size=config["intermediate_size"], dtype=dtype, param_dtype=dtype)
    return _seeded_model()(NemotronHConfig(**{**sizes, **overrides}))


def centre_down_projections(params):
    """The configuration's ``assumed.weights``: every ``relu(.)^2`` MLP's
    down projection (each expert's, the shared expert's, a dense layer's)
    with its N(0, 0.02) draw centred over the hidden axis. ``relu(.)^2`` is
    never negative, so every hidden unit has a positive mean, and through an
    uncentred draw that mean is one fixed vector added to every token:
    eleven layers deep it aligns the tokens' states and the router sends 59%
    of its picks to 22 of 512 experts (``PERF.md`` section 6, PR 30).
    Training removes it; the benchmark's seeded draw leaves it out here, and
    the package's initialiser stays the family's plain one."""
    def centre(path, w):
        names = [getattr(k, "key", None) for k in path]
        if names[-2:] != ["down_proj", "kernel"]:
            return w
        w32 = w.astype(jnp.float32)
        return (w32 - w32.mean(axis=-2, keepdims=True)).astype(w.dtype)
    return jax.tree_util.tree_map_with_path(centre, params)


def _seeded_model():
    """The package's model class with one thing changed: ``init`` returns
    the benchmark's seeded weights (:func:`centre_down_projections` over the
    package's own draw, unboxed). The runner makes the weights by
    ``model.init`` and gives the family no later hand on them."""
    import flax.linen as nn
    from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM

    class SeededNemotronH(NemotronHForCausalLM):
        def init(self, *args, **kwargs):
            variables = nn.meta.unbox(super().init(*args, **kwargs))
            return {**variables, "params": centre_down_projections(variables["params"])}

    return SeededNemotronH


def op_label(text, stats=None):
    """Names this family's kernels in a device trace from the instruction
    names XLA derives (the events carry no other metadata): the grouped
    expert matmuls (``%gmm``, or XLA's ``%ragged-dot``) are
    ``pallas:moe:matmul``; a custom call named after a recurrence kernel
    (``ssm_*``) would be ``pallas:ssm``; none is in the tree (the recurrence
    runs as XLA fusions, PERF.md section 6, PR 30), and the cell's
    attention is XLA's too."""
    name = trace.op_name(text).lstrip("%")
    if name.startswith(("gmm", "ragged-dot")):
        return "pallas:moe:matmul"
    if trace.is_custom_call(text):
        return "pallas:ssm" if name.startswith("ssm_") else "pallas:other"
    return trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict. Only
    views and reshapes: the leaves stay as and where they are served."""
    flat = {"embed": params["embed_tokens"], "norm": params["norm_f"]["weight"],
            "head": params["lm_head"]["kernel"]}
    n_layer = sum(1 for k in params if k.startswith("layers_"))
    for i in range(n_layer):
        blk, pre = params[f"layers_{i}"], f"layers.{i}."
        mix = blk["mixer"]
        flat[pre + "ln"] = blk["norm"]["weight"]
        if "in_proj" in mix:
            flat.update({pre + "in_proj": mix["in_proj"]["kernel"],
                         pre + "conv_w": mix["conv1d_weight"], pre + "conv_b": mix["conv1d_bias"],
                         pre + "dt_bias": mix["dt_bias"], pre + "A_log": mix["A_log"],
                         pre + "D": mix["D"], pre + "norm_w": mix["norm_weight"],
                         pre + "out_proj": mix["out_proj"]["kernel"]})
        elif "q_proj" in mix:
            e = mix["q_proj"]["kernel"].shape[0]
            flat.update({pre + "wq": mix["q_proj"]["kernel"].reshape(e, -1),   # heads contiguous
                         pre + "wk": mix["k_proj"]["kernel"].reshape(e, -1),
                         pre + "wv": mix["v_proj"]["kernel"].reshape(e, -1),
                         pre + "wo": mix["o_proj"]["kernel"].reshape(-1, e)})
        else:
            bank = mix["experts"]["deepspeed_experts"]
            flat.update({pre + "router": mix["gate"]["wg"],
                         pre + "router_bias": mix["gate"]["e_score_correction_bias"],
                         pre + "latent_down": mix["latent_down"]["kernel"],
                         pre + "latent_up": mix["latent_up"]["kernel"],
                         pre + "w1": bank["up_proj"]["kernel"],
                         pre + "w2": bank["down_proj"]["kernel"],
                         pre + "shared_w1": mix["shared_expert"]["up_proj"]["kernel"],
                         pre + "shared_w2": mix["shared_expert"]["down_proj"]["kernel"]})
    return flat


_embed = jax.jit(ref.embed)
_block = jax.jit(ref.block, static_argnums=(2, 3))
_head = jax.jit(ref.head)
_mamba_and_state = jax.jit(lambda bp, x, sizes: ref.mamba(bp, x, sizes, final_state=True),
                           static_argnums=(2,))


#: what :func:`model` last built: the pattern, the head counts and the
#: routing sizes are given by no weight's shape, and the runner hands
#: :func:`reference_logits` the weights, the ids and ``n_head`` only
_built = {}


def reference_logits(flat, ids, n_head, sizes=None):
    """Reference logits [B, L, V], a layer to a program, for the
    configuration :func:`model` was last called with (or ``sizes``)."""
    sizes = sizes or _built["sizes"]
    top = {k: flat[k] for k in ("embed", "norm", "head")}
    x = _embed(top, ids)
    for i, kind in enumerate(sizes.pattern):
        x = _block(ref.block_params(flat, i), x, kind, sizes)
    return _head(top, x)


def reference_final_states(flat, ids, sizes=None):
    """The reference's recurrent state after the last of ``ids`` [B, L], one
    [B, heads, head dim, state] a Mamba layer in the pattern's order: what a
    server that was fed ``ids`` carries in those slots' ``ssm_state``. No
    runner reads it yet: the serving runner's comparison sees emitted tokens
    only (``PERF.md`` section 7, PR 30); ``tools/nemotron_h_controls.py``
    and the tests hold a scheduler's cache to it."""
    sizes = sizes or _built["sizes"]
    x = _embed({k: flat[k] for k in ("embed", "norm", "head")}, ids)
    states = []
    for i, kind in enumerate(sizes.pattern):
        if kind == "M":
            x, state = _mamba_and_state(ref.block_params(flat, i), x, sizes)
            states.append(state)
        else:
            x = _block(ref.block_params(flat, i), x, kind, sizes)
    return states
