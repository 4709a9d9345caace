"""Operations and bytes one tick of a JoyAI-LLM-Flash server needs, computed
from the published sizes and the tick's own shapes. Kept with the benchmark
so that the program cannot change its own yardstick: every size and every
formula here is the benchmark's. (Two *arguments* the readers pass in are
not: ``rows`` and ``touched`` come from the program's device-side counters,
``lib/nemotron_h_ticks.py`` says why; left out, the even router's expectation
stands in.) What the program pads (parked slots, a short chunk, key blocks
past a slot's live length, the absorbed step's read of dead positions) is
never counted: the counts are the mathematics'.

The latent attention has two forms of the same numbers, and a tick is owed
the cheaper one for its shape (:func:`attention_flops`): ``expanded`` makes
each attended position's keys and values from its latent once a sequence a
pass (2 x rank x heads x (nope + value)) and then pays heads x (nope + rope +
value) a query-key pair; ``absorbed`` makes none and pays heads x (rank +
rope + rank) a pair, and two small projections a query. One query a sequence
(a decode tick) is cheaper absorbed; past ~170 queries a sequence (a prefill
chunk) expanded.

``config`` is the parsed configuration file (keys as published, with
``n_routed_experts`` the experts *held* and ``n_routed_experts_published``
the router's width); a tick is ``tokens`` positions of ``sequences`` slots
fed to one forward pass, ``kv_positions`` latent-pool positions (a layer)
its attention has to read: the fed slots' live lengths, summed.
"""

WEIGHT_BYTES = 2          # bf16 weights, as served
LATENT_BYTES = 2          # bf16 latent pool


def layers(config, kind):
    """``"D"`` the leading dense layers, ``"E"`` the expert layers, ``"A"``
    every layer (each has the attention)."""
    dense = config["first_k_dense_replace"]
    return {"D": dense, "E": config["num_hidden_layers"] - dense,
            "A": config["num_hidden_layers"]}[kind]


def _heads(config):
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"], config["kv_lora_rank"])


def latent_width(config):
    """Values the pool holds a position a layer: the latent and the rope key."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def attention_params(config):
    """The five projections, the two inner norms and the block's RMSNorm."""
    e, rq = config["hidden_size"], config["q_lora_rank"]
    heads, dn, dr, dv, rank = _heads(config)
    return (e * rq + rq + rq * heads * (dn + dr) + e * (rank + dr) + rank
            + rank * heads * (dn + dv) + heads * dv * e + e)


def dense_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"] + config["hidden_size"]


def expert_params(config):
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def moe_shared_params(config):
    """What every chip holds of an expert layer beside its experts: the
    router over all the experts with its bias, the shared expert, the
    block's second RMSNorm."""
    e = config["hidden_size"]
    every = config["n_routed_experts_published"]
    return every * e + every + config["n_shared_experts"] * expert_params(config) + e


def head_params(config):
    """The head's slice and the final norm; the embedding is a lookup."""
    return config["hidden_size"] * config["vocab_size"] + config["hidden_size"]


def params_held(config):
    """Every parameter this chip holds, the table included."""
    return (layers(config, "A") * attention_params(config)
            + layers(config, "D") * dense_params(config)
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
    reach, each taking k distinct of all the experts evenly."""
    share = config["num_experts_per_tok"] / config["n_routed_experts_published"]
    return config["n_routed_experts"] * (1.0 - (1.0 - share) ** tokens)


def expert_bytes(config, tokens, touched=None):
    """Bytes of held expert weights a tick streams over all layers."""
    touched = experts_touched(config, tokens) if touched is None else touched
    return layers(config, "E") * touched * expert_params(config) * WEIGHT_BYTES


def expert_flops(config, tokens, rows=None):
    """FLOPs of the routed matmuls of the experts held here: ``rows`` rows
    over all layers, as the program counted them, or an even router's."""
    rows = layers(config, "E") * tokens * picks_here(config) if rows is None else rows
    return rows * 2 * expert_params(config)


def attention_flops(config, queries, pairs, expanded_positions=None):
    """One layer's attention proper, beside its five projections:
    ``queries`` queries making ``pairs`` query-key pairs in all. With
    ``expanded_positions`` (cached positions whose keys and values are made
    from their latent, once each) the expanded form; with None the absorbed
    form, whose queries pay the two absorbing projections instead."""
    heads, dn, dr, dv, rank = _heads(config)
    if expanded_positions is not None:
        return (2 * pairs * heads * (dn + dr + dv)
                + 2 * expanded_positions * rank * heads * (dn + dv))
    return 2 * pairs * heads * (2 * rank + dr) + 2 * queries * heads * rank * (dn + dv)


def tick_attention_flops(config, tokens, sequences, kv_positions):
    """One layer's attention for a tick, in the cheaper form: a query of a
    sequence that ends the tick at length ``L`` and was fed ``c`` tokens
    attends ``L - (c - 1) / 2`` positions on average."""
    if not sequences:
        return 0.0
    pairs = tokens * max(kv_positions / sequences - (tokens / sequences - 1) / 2.0, 1.0)
    return min(attention_flops(config, tokens, pairs),
               attention_flops(config, tokens, pairs, expanded_positions=kv_positions))


def decode_kernel_flops(config, pairs):
    """One layer's absorbed-step kernel (``ops/pallas/latent_decode.py``):
    scores against latent and rope rows, the weighted sum against the latent
    rows; the absorbing projections run outside it."""
    heads, _, dr, _, rank = _heads(config)
    return 2 * pairs * heads * (2 * rank + dr)


def decode_kernel_bytes(config, queries, kv_positions):
    """Bytes that kernel has to move for one layer: the live positions of
    the fed slots' pools once, the queries in and the mixed latents out."""
    heads, _, dr, _, rank = _heads(config)
    return (kv_positions * latent_width(config) * LATENT_BYTES
            + queries * heads * (2 * rank + dr) * WEIGHT_BYTES)


def latent_bytes(config, tokens, kv_positions):
    """Bytes of latent pool a tick reads (the fed slots' live positions,
    once a layer) and writes (the new tokens' rows)."""
    return layers(config, "A") * (kv_positions + tokens) * latent_width(config) * LATENT_BYTES


def tick_bytes(config, tokens, sequences, kv_positions, touched=None):
    """Bytes a tick has to move when nothing but weights, the latent
    positions attended and the new latent rows touch memory."""
    dense = (layers(config, "A") * attention_params(config)
             + layers(config, "D") * dense_params(config)
             + layers(config, "E") * moe_shared_params(config) + head_params(config))
    return (expert_bytes(config, tokens, touched) + dense * WEIGHT_BYTES
            + latent_bytes(config, tokens, kv_positions))


def tick_flops(config, tokens, sequences, kv_positions, rows=None):
    """FLOPs a tick's mathematics needs: every token's five attention
    projections, the attention in its cheaper form, the dense layer or the
    router and shared expert, the routed experts held here; the head for the
    one position of each sequence whose logits are used."""
    e = config["hidden_size"]
    heads, dn, dr, dv, rank = _heads(config)
    norms = config["q_lora_rank"] + rank + e
    per_token = (layers(config, "A") * 2 * (attention_params(config) - norms)
                 + layers(config, "D") * 2 * (dense_params(config) - e)
                 + layers(config, "E") * 2 * (moe_shared_params(config) - e
                                              - config["n_routed_experts_published"]))
    return (tokens * per_token
            + layers(config, "A") * tick_attention_flops(config, tokens, sequences, kv_positions)
            + expert_flops(config, tokens, rows) + sequences * 2 * (head_params(config) - e))


def moe_kernel_bytes(config, tokens, touched=None, rows=None):
    """Bytes the grouped expert matmuls move at the least: the touched held
    experts' weights once, each routed row into the gate and up matmuls once
    (one read), their two results out and the product in, the result out."""
    rows = layers(config, "E") * tokens * picks_here(config) if rows is None else rows
    e, f = config["hidden_size"], config["moe_intermediate_size"]
    return expert_bytes(config, tokens, touched) + rows * (2 * e + 3 * f) * WEIGHT_BYTES


def roofline_ms(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"] * 1e3
    t_memory = nbytes / peaks["hbm_bytes_s"] * 1e3
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
