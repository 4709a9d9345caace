"""Operations and bytes one tick of a Nemotron-H server needs, computed from
the published sizes and the tick's own shapes. Kept with the benchmark so
that the program cannot change its own yardstick: every size and every
formula here is the benchmark's. (Two *arguments* the readers pass in are
not: ``rows`` and ``touched`` come from the program's device-side counters,
``lib/nemotron_h_ticks.py`` says why; left out, the even router's
expectation stands in.) What the program pads
(parked slots, a short chunk), computes twice, or computes in another form
(the chunked scan does other work than the recurrence it equals) is never
counted: the counts are the mathematics', the recurrence a position a head.

``config`` is the parsed configuration file (keys as published, with
``n_routed_experts`` the experts *held* and ``n_routed_experts_published``
the router's width); a tick is ``tokens`` positions of ``sequences`` slots
fed to one forward pass, ``kv_positions`` cache positions its attention
layers have to read.
"""

WEIGHT_BYTES = 2          # bf16 weights, as served
STATE_BYTES = 4           # float32 recurrent state
TAIL_BYTES = 2            # bf16 convolution tail


def layers(config, kind):
    return config["hybrid_override_pattern"].count(kind)


def _mamba_sizes(config):
    heads, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    inner = heads * hd
    return heads, hd, inner, inner + 2 * config["n_groups"] * config["ssm_state_size"]


def mamba_params(config):
    """One Mamba-2 layer: in and out projections, the convolution and its
    bias, ``dt_bias`` / ``A_log`` / ``D`` a head, the gated norm's weight and
    the block's RMSNorm."""
    h = config["hidden_size"]
    heads, _, inner, conv = _mamba_sizes(config)
    return (h * (inner + conv + heads) + (config["conv_kernel"] + 1) * conv + 3 * heads
            + inner + inner * h + h)


def attention_params(config):
    h, hd = config["hidden_size"], config["head_dim"]
    return (2 * h * config["num_attention_heads"] * hd
            + 2 * h * config["num_key_value_heads"] * hd + h)


def expert_params(config):
    """One routed expert: two matrices between the latent space and its width."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def moe_shared_params(config):
    """What every chip holds of an expert layer beside its experts: the
    router over all the experts with its bias, both latent projections, the
    shared expert, the block's RMSNorm."""
    h = config["hidden_size"]
    every = config["n_routed_experts_published"]
    return (h * every + every + 2 * h * config["moe_latent_size"]
            + 2 * h * config["moe_shared_expert_intermediate_size"] + h)


def head_params(config):
    """The head's slice and the final norm; the embedding is a lookup."""
    return config["hidden_size"] * config["vocab_size"] + config["hidden_size"]


def params_held(config):
    """Every parameter this chip holds, the table included."""
    return (layers(config, "M") * mamba_params(config) + layers(config, "*") * attention_params(config)
            + layers(config, "E") * (moe_shared_params(config)
                                     + config["n_routed_experts"] * expert_params(config))
            + head_params(config) + config["hidden_size"] * config["vocab_size"])


def picks_here(config):
    """Expected experts held here among a token's ``num_experts_per_tok``
    when the router chooses evenly: k x held / published."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["n_routed_experts_published"])


def experts_touched(config, tokens):
    """Expected number of held experts of one layer that ``tokens`` tokens
    reach, each taking k distinct of all the experts evenly:
    held x (1 - (1 - k / published)^tokens)."""
    share = config["num_experts_per_tok"] / config["n_routed_experts_published"]
    return config["n_routed_experts"] * (1.0 - (1.0 - share) ** tokens)


def state_bytes_per_slot(config):
    """Recurrent state and convolution tail one slot holds over all layers."""
    heads, hd, _, conv = _mamba_sizes(config)
    return layers(config, "M") * (heads * hd * config["ssm_state_size"] * STATE_BYTES
                                  + (config["conv_kernel"] - 1) * conv * TAIL_BYTES)


def kv_bytes_per_position(config, int8=True):
    """Bytes one cache position holds in one attention layer."""
    heads, hd = config["num_key_value_heads"], config["head_dim"]
    return 2 * heads * (hd + 2) if int8 else 2 * heads * hd * 2


def expert_bytes(config, tokens, touched=None):
    """Bytes of held expert weights a tick streams over all layers:
    ``touched`` held experts a layer, as the program counted them, or what
    ``tokens`` tokens reach under an even router."""
    touched = experts_touched(config, tokens) if touched is None else touched
    return layers(config, "E") * touched * expert_params(config) * WEIGHT_BYTES


def expert_flops(config, tokens, rows=None):
    """FLOPs of the routed matmuls of the experts held here: ``rows`` rows
    over all layers, as the program counted them, or an even router's."""
    rows = layers(config, "E") * tokens * picks_here(config) if rows is None else rows
    return rows * 2 * expert_params(config)


def scan_flops_per_token(config):
    """The recurrence for one position of one layer: decay, outer product
    and accumulate into each state element (3), read it out against C (2);
    and the convolution's 4 taps."""
    heads, hd, _, conv = _mamba_sizes(config)
    return 5 * heads * hd * config["ssm_state_size"] + 2 * config["conv_kernel"] * conv


def tick_bytes(config, tokens, sequences, kv_positions, int8_kv=True, touched=None):
    """Bytes a tick has to move when nothing but weights, the fed slots'
    recurrent state (read and written once), the cache positions attended
    and the new cache rows touch memory."""
    dense = (layers(config, "M") * mamba_params(config) + layers(config, "*") * attention_params(config)
             + layers(config, "E") * moe_shared_params(config) + head_params(config))
    kv = layers(config, "*") * (kv_positions + tokens) * kv_bytes_per_position(config, int8_kv)
    state = 2 * sequences * state_bytes_per_slot(config)
    return expert_bytes(config, tokens, touched) + dense * WEIGHT_BYTES + kv + state


def tick_flops(config, tokens, sequences, kv_positions, rows=None):
    """FLOPs a tick's mathematics needs: every token's Mamba projections,
    convolution and recurrence, attention projections and the scores and
    values against the positions each query attends, the router, latent and
    shared matmuls, the routed experts held here; the head for the one
    position of each sequence whose logits are used."""
    h = config["hidden_size"]
    _, _, inner, conv = _mamba_sizes(config)
    mamba = 2 * h * (inner + conv + config["mamba_num_heads"]) + 2 * inner * h \
        + scan_flops_per_token(config)
    attended = kv_positions / max(sequences, 1)
    attn = 2 * (attention_params(config) - h) \
        + 4 * config["num_attention_heads"] * config["head_dim"] * attended
    shared = 2 * (moe_shared_params(config) - h)
    per_token = (layers(config, "M") * mamba + layers(config, "*") * attn
                 + layers(config, "E") * shared)
    return (tokens * per_token + expert_flops(config, tokens, rows)
            + sequences * 2 * head_params(config))


def moe_kernel_bytes(config, tokens, touched=None, rows=None):
    """Bytes the grouped expert matmuls move at the least: the touched held
    experts' weights once, each routed row into and out of both matmuls."""
    rows = layers(config, "E") * tokens * picks_here(config) if rows is None else rows
    z, f = config["moe_latent_size"], config["moe_intermediate_size"]
    return expert_bytes(config, tokens, touched) + rows * 2 * (z + f) * WEIGHT_BYTES


def roofline_ms(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"] * 1e3
    t_memory = nbytes / peaks["hbm_bytes_s"] * 1e3
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
