"""Operations and bytes one tick of a dots3-note server needs, computed from
the published sizes and the tick's own shapes. Kept with the benchmark so
that the program cannot change its own yardstick: every size and every
formula here is the benchmark's. (Some *arguments* the readers pass in are
not: expert ``rows`` and ``touched`` and the attention's ``pairs`` come from
the program's device-side counters, ``lib/dots3_note_ticks.py`` says which
and why.) What the program pads or reads beyond the mathematics (parked
slots, a short chunk, key blocks past a slot's live length, the latent of a
live position no query chose, the half of a ring outside every window) is
never counted.

Two kinds of layer (``layer_types``). A **full** layer scores every live
position of a sequence with its indexer (``index_n_heads`` x
``index_head_dim`` x 2 a query-position pair, one 256 B key a position) and
attends the ``index_topk`` it chose: a query owes the attention of those
pairs only, and their latents (1,152 B each) are all it has to read. A
**sliding** layer attends the window: ``sliding_window_size`` positions a
query, 2,176 B each. Either attention is owed in the cheaper of the latent
attention's two forms (``lib/opcounts_joyai_llm_flash.py`` has the
derivation): absorbed for one query a sequence, expanded for a chunk.

``config`` is the parsed configuration file (keys as published, with
``n_routed_experts`` the experts *held* and ``n_routed_experts_published``
the router's width).
"""

WEIGHT_BYTES = 2          # bf16 weights, as served
CACHE_BYTES = 2           # bf16 latents and index keys


def layer_kinds(config):
    return ["sliding" if t == "sliding_attention" else "full" for t in config["layer_types"]]


def layers(config, kind):
    """``"D"`` the leading dense layers, ``"E"`` the expert layers, ``"F"``
    the full (indexed) attention layers, ``"S"`` the sliding ones."""
    dense = config["first_k_dense_replace"]
    kinds = layer_kinds(config)
    return {"D": dense, "E": config["num_hidden_layers"] - dense,
            "F": kinds.count("full"), "S": kinds.count("sliding")}[kind]


def heads(config, kind):
    """``(heads, nope, rope, value, kv rank, q rank)`` of a kind of layer."""
    pre = "swa_" if kind == "S" else ""
    return (config[pre + "num_attention_heads"], config[pre + "qk_nope_head_dim"],
            config[pre + "qk_rope_head_dim"], config[pre + "v_head_dim"],
            config[pre + "kv_lora_rank"], config[pre + "q_lora_rank"])


def latent_width(config, kind):
    """Values the cache holds a position a layer of this kind."""
    _, _, dr, _, rank, _ = heads(config, kind)
    return rank + dr


def attention_matrices(config, kind):
    """The five projections and the gate: what a token multiplies by."""
    e = config["hidden_size"]
    h, dn, dr, dv, rank, rq = heads(config, kind)
    return e * rq + rq * h * (dn + dr) + e * (rank + dr) + rank * h * (dn + dv) + h * dv * e + e * h


def attention_params(config, kind):
    """Those, the two inner norms and the block's RMSNorm."""
    _, _, _, _, rank, rq = heads(config, kind)
    return attention_matrices(config, kind) + rq + rank + config["hidden_size"]


def indexer_matrices(config):
    j, d = config["index_n_heads"], config["index_head_dim"]
    return config["q_lora_rank"] * j * d + config["hidden_size"] * (d + j)


def indexer_params(config):
    """Those and the key's LayerNorm (scale and bias)."""
    return indexer_matrices(config) + 2 * config["index_head_dim"]


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
    return (layers(config, "F") * (attention_params(config, "F") + indexer_params(config))
            + layers(config, "S") * attention_params(config, "S")
            + layers(config, "D") * dense_params(config)
            + layers(config, "E") * (moe_shared_params(config)
                                     + config["n_routed_experts"] * expert_params(config))
            + head_params(config) + config["hidden_size"] * config["vocab_size"])


def cache_bytes(config, slots, positions, ring):
    """Bytes of the slot cache: a full layer's latent and index key over
    every position, a sliding layer's latent over its ring."""
    full = (latent_width(config, "F") + config["index_head_dim"]) * positions
    return slots * CACHE_BYTES * (layers(config, "F") * full
                                  + layers(config, "S") * latent_width(config, "S") * ring)


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


def attention_flops(config, kind, queries, pairs, expanded_positions=None):
    """One layer's attention proper: ``queries`` queries making ``pairs``
    query-key pairs. With ``expanded_positions`` (positions whose keys and
    values are made from their latent, once each) the expanded form; with
    None the absorbed form."""
    h, dn, dr, dv, rank, _ = heads(config, kind)
    if expanded_positions is not None:
        return 2 * pairs * h * (dn + dr + dv) + 2 * expanded_positions * rank * h * (dn + dv)
    return 2 * pairs * h * (2 * rank + dr) + 2 * queries * h * rank * (dn + dv)


def cheaper_attention_flops(config, kind, queries, pairs, positions):
    return min(attention_flops(config, kind, queries, pairs),
               attention_flops(config, kind, queries, pairs, expanded_positions=positions))


def index_flops(config, pairs):
    """One full layer's index scores: every query against every position
    live before it, ``index_n_heads`` products of ``index_head_dim``."""
    return 2 * pairs * config["index_n_heads"] * config["index_head_dim"]


def tick_pairs(config, tokens, sequences, kv_positions):
    """Query-position pairs one layer of each kind owes a tick whose
    ``sequences`` sequences end it ``kv_positions`` long in all, from the
    mean sequence: ``{"live", "selected", "window"}``. A query of a sequence
    that ends at ``L`` having been fed ``c`` sees ``L - (c - 1) / 2``
    positions on average."""
    if not sequences:
        return {"live": 0.0, "selected": 0.0, "window": 0.0}
    seen = max(kv_positions / sequences - (tokens / sequences - 1) / 2.0, 1.0)
    return {"live": tokens * seen, "selected": tokens * min(seen, config["index_topk"]),
            "window": tokens * min(seen, config["sliding_window_size"])}


def window_positions(config, tokens, sequences, kv_positions):
    """Ring positions the windows of a tick's queries cover, all sequences."""
    if not sequences:
        return 0.0
    return sequences * min(kv_positions / sequences,
                           tokens / sequences + config["sliding_window_size"] - 1)


def selected_positions(config, tokens, sequences, kv_positions):
    """Positions of a full layer's pool a tick's queries chose, all
    sequences: ``index_topk`` a query at the most, and no more than are live
    (a chunk's queries choose differently: together they reach most)."""
    if not sequences:
        return 0.0
    return sequences * min(kv_positions / sequences,
                           tokens / sequences * config["index_topk"])


def tick_flops(config, tokens, sequences, kv_positions, rows=None, pairs=None):
    """FLOPs a tick's mathematics needs: every token's projections (the
    indexer's among them), the index scores of every live pair, the
    attention of the chosen pairs and of the windows in the cheaper form,
    the dense layer or the router and shared expert, the routed experts held
    here, the head for the one position a sequence whose logits are used."""
    e = config["hidden_size"]
    pairs = pairs or tick_pairs(config, tokens, sequences, kv_positions)
    per_token = 2 * (layers(config, "F") * (attention_matrices(config, "F")
                                            + indexer_matrices(config))
                     + layers(config, "S") * attention_matrices(config, "S")
                     + layers(config, "D") * (dense_params(config) - e)
                     + layers(config, "E") * (moe_shared_params(config) - e
                                              - config["n_routed_experts_published"]))
    full = (index_flops(config, pairs["live"])
            + cheaper_attention_flops(config, "F", tokens, pairs["selected"],
                                      selected_positions(config, tokens, sequences, kv_positions)))
    sliding = cheaper_attention_flops(config, "S", tokens, pairs["window"],
                                      window_positions(config, tokens, sequences, kv_positions))
    return (tokens * per_token + layers(config, "F") * full + layers(config, "S") * sliding
            + expert_flops(config, tokens, rows) + sequences * 2 * (head_params(config) - e))


def cache_read_bytes(config, tokens, sequences, kv_positions):
    """Bytes of the cache a tick has to read and write: a full layer's index
    keys of every live position and the latents its queries chose, a sliding
    layer's windows, and the new tokens' rows of each."""
    full = (kv_positions * config["index_head_dim"]
            + selected_positions(config, tokens, sequences, kv_positions)
            * latent_width(config, "F")
            + tokens * (latent_width(config, "F") + config["index_head_dim"]))
    sliding = ((window_positions(config, tokens, sequences, kv_positions) + tokens)
               * latent_width(config, "S"))
    return CACHE_BYTES * (layers(config, "F") * full + layers(config, "S") * sliding)


def tick_bytes(config, tokens, sequences, kv_positions, touched=None):
    """Bytes a tick has to move when nothing but weights and the cache rows
    its mathematics reads and writes touch memory."""
    dense = (layers(config, "F") * (attention_params(config, "F") + indexer_params(config))
             + layers(config, "S") * attention_params(config, "S")
             + layers(config, "D") * dense_params(config)
             + layers(config, "E") * moe_shared_params(config) + head_params(config))
    return (expert_bytes(config, tokens, touched) + dense * WEIGHT_BYTES
            + cache_read_bytes(config, tokens, sequences, kv_positions))


def moe_kernel_bytes(config, tokens, touched=None, rows=None):
    """Bytes the grouped expert matmuls move at the least: the touched held
    experts' weights once, each routed row into the gate and up matmuls once
    (one read), their two results out and the product in, the result out."""
    rows = layers(config, "E") * tokens * picks_here(config) if rows is None else rows
    e, f = config["hidden_size"], config["moe_intermediate_size"]
    return expert_bytes(config, tokens, touched) + rows * (2 * e + 3 * f) * WEIGHT_BYTES


# -- the four kernels of a full layer, one layer each -----------------------
def index_kernel(config, queries, pairs, kv_positions):
    """``(FLOPs, bytes)`` of one layer's index scores (``dsa_index_decode`` /
    ``dsa_index_prefill``): the products of every live pair; each live key
    once, the queries and weights in, one float32 score a pair out."""
    j, d = config["index_n_heads"], config["index_head_dim"]
    return (index_flops(config, pairs),
            kv_positions * d * CACHE_BYTES + queries * j * (d * CACHE_BYTES + 4) + pairs * 4)


def selected_decode_kernel(config, queries, pairs):
    """``(FLOPs, bytes)`` of one layer's absorbed step over the chosen
    positions (``dsa_decode``): heads x (2 x rank + rope) x 2 a chosen pair;
    each chosen latent once (one query a sequence: its pairs are its
    positions), the queries in and the mixed latents out."""
    h, _, dr, _, rank, _ = heads(config, "F")
    return (2 * pairs * h * (2 * rank + dr),
            pairs * latent_width(config, "F") * CACHE_BYTES
            + queries * h * (2 * rank + dr) * WEIGHT_BYTES)


def selected_walk_kernel(config, queries, pairs, positions):
    """``(FLOPs, bytes)`` of one layer's expanded walk over the chosen pairs
    of a chunk (``dsa_prefill_walk``): heads x (nope + rope + value) x 2 a
    chosen pair and the keys and values of each of the ``positions`` some
    query chose, made from its latent once; those latents read once, the
    queries in and the heads' outputs out."""
    h, dn, dr, dv, rank, _ = heads(config, "F")
    return (attention_flops(config, "F", queries, pairs, expanded_positions=positions),
            positions * latent_width(config, "F") * CACHE_BYTES
            + queries * h * (dn + dr + dv) * WEIGHT_BYTES)


def roofline_ms(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"] * 1e3
    t_memory = nbytes / peaks["hbm_bytes_s"] * 1e3
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
