"""Operations and bytes one tick of a DeepSeek-V3.2 server needs, computed
from the published sizes and the tick's own shapes. Kept with the benchmark
so that the program cannot change its own yardstick: every size and every
formula here is the benchmark's. (Some *arguments* the readers pass in are
not: expert ``rows`` and ``touched`` and the attention's ``pairs`` come from
the program's device-side counters, ``lib/deepseek_v32_ticks.py`` says which
and why.) What the program pads or reads beyond the mathematics (parked
slots, a short chunk, key blocks past a slot's live length, the latent of a
live position no query chose) is never counted.

Every layer is dots3-note's **full** layer without its gate: the indexer
scores every live position of a sequence (``index_n_heads`` x
``index_head_dim`` x 2 a query-position pair, one 256 B key a position) and the
attention is owed the ``index_topk`` chosen pairs alone, their latents (1,152 B
each) all it has to read, in the cheaper of the latent attention's two forms
(``lib/opcounts_joyai_llm_flash.py`` has the derivation). The counts that do
not see the difference (an expert, the indexer, the four kernels of an indexed
layer, the roofline itself) are ``lib/opcounts_dots3_note.py``'s own, imported
and not copied; the ones that do (no gate, no sliding layers, every layer
indexed) are written here. Group-limited routing changes which experts a token
takes, not how many: the router's FLOPs are its matrix's.

``config`` is the parsed configuration file (keys as published, with
``n_routed_experts`` the experts *held* and ``n_routed_experts_published``
the router's width).
"""

from benchmarks.lib.opcounts_dots3_note import (CACHE_BYTES, WEIGHT_BYTES,  # noqa: F401
                                                attention_flops, dense_params, expert_flops,
                                                expert_params, head_params, index_flops,
                                                index_kernel, indexer_matrices, indexer_params,
                                                moe_shared_params, picks_here, roofline_ms,
                                                selected_decode_kernel, selected_positions,
                                                selected_walk_kernel)

KIND = "F"      # every layer is what ``opcounts_dots3_note`` calls a full layer


def layers(config, kind):
    """``"D"`` the leading dense layers, ``"E"`` the expert layers, ``"F"``
    the (indexed) attention layers: all of them."""
    dense = config["first_k_dense_replace"]
    return {"D": dense, "E": config["num_hidden_layers"] - dense,
            "F": config["num_hidden_layers"]}[kind]


def latent_width(config):
    """Values the cache holds a position a layer."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def attention_matrices(config):
    """The five projections: what a token multiplies by (no gate here)."""
    e, h = config["hidden_size"], config["num_attention_heads"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    rank, rq = config["kv_lora_rank"], config["q_lora_rank"]
    return e * rq + rq * h * (dn + dr) + e * (rank + dr) + rank * h * (dn + dv) + h * dv * e


def attention_params(config):
    """Those, the two inner norms and the block's RMSNorm."""
    return (attention_matrices(config) + config["q_lora_rank"] + config["kv_lora_rank"]
            + config["hidden_size"])


def params_held(config):
    """Every parameter this chip holds, the table included."""
    return (layers(config, "F") * (attention_params(config) + indexer_params(config))
            + layers(config, "D") * dense_params(config)
            + layers(config, "E") * (moe_shared_params(config)
                                     + config["n_routed_experts"] * expert_params(config))
            + head_params(config) + config["hidden_size"] * config["vocab_size"])


def cache_bytes(config, slots, positions):
    """Bytes of the slot cache: every layer's latent and index key over every
    position."""
    return (slots * CACHE_BYTES * layers(config, "F")
            * (latent_width(config) + config["index_head_dim"]) * positions)


def group_kept_share(config):
    """The share of tokens whose kept groups include the held experts' group
    under an even router: ``topk_group / n_group`` (the held experts lie in
    one group)."""
    return config["topk_group"] / config["n_group"]


def experts_touched(config, tokens):
    """Expected number of held experts of one layer that ``tokens`` tokens
    reach, each taking k distinct of all the experts evenly (the group limit
    leaves an even router's marginal as it was: k / published an expert)."""
    share = config["num_experts_per_tok"] / config["n_routed_experts_published"]
    return config["n_routed_experts"] * (1.0 - (1.0 - share) ** tokens)


def expert_bytes(config, tokens, touched=None):
    """Bytes of held expert weights a tick streams over all layers."""
    touched = experts_touched(config, tokens) if touched is None else touched
    return layers(config, "E") * touched * expert_params(config) * WEIGHT_BYTES


def routed_flops(config, tokens, rows=None):
    """FLOPs of the routed matmuls of the experts held here: ``rows`` rows over
    all layers, as the program counted them, or an even router's."""
    rows = layers(config, "E") * tokens * picks_here(config) if rows is None else rows
    return expert_flops(config, tokens, rows)


def tick_pairs(config, tokens, sequences, kv_positions):
    """Query-position pairs one layer owes a tick whose ``sequences`` sequences
    end it ``kv_positions`` long in all, from the mean sequence: ``{"live",
    "selected"}``. A query of a sequence that ends at ``L`` having been fed
    ``c`` sees ``L - (c - 1) / 2`` positions on average."""
    if not sequences:
        return {"live": 0.0, "selected": 0.0}
    seen = max(kv_positions / sequences - (tokens / sequences - 1) / 2.0, 1.0)
    return {"live": tokens * seen, "selected": tokens * min(seen, config["index_topk"])}


def tick_flops(config, tokens, sequences, kv_positions, rows=None, pairs=None):
    """FLOPs a tick's mathematics needs: every token's projections (the
    indexer's among them), the index scores of every live pair, the attention
    of the chosen pairs in the cheaper form, the dense layer or the router and
    shared expert, the routed experts held here, the head for the one position
    a sequence whose logits are used."""
    e = config["hidden_size"]
    pairs = pairs or tick_pairs(config, tokens, sequences, kv_positions)
    per_token = 2 * (layers(config, "F") * (attention_matrices(config) + indexer_matrices(config))
                     + layers(config, "D") * (dense_params(config) - e)
                     + layers(config, "E") * (moe_shared_params(config) - e
                                              - config["n_routed_experts_published"]))
    chosen_at = selected_positions(config, tokens, sequences, kv_positions)
    attention = min(attention_flops(config, KIND, tokens, pairs["selected"]),
                    attention_flops(config, KIND, tokens, pairs["selected"],
                                    expanded_positions=chosen_at))
    return (tokens * per_token
            + layers(config, "F") * (index_flops(config, pairs["live"]) + attention)
            + routed_flops(config, tokens, rows) + sequences * 2 * (head_params(config) - e))


def cache_read_bytes(config, tokens, sequences, kv_positions):
    """Bytes of the cache a tick has to read and write: a layer's index keys
    of every live position, the latents its queries chose, and the new tokens'
    rows of each."""
    a_layer = (kv_positions * config["index_head_dim"]
               + selected_positions(config, tokens, sequences, kv_positions) * latent_width(config)
               + tokens * (latent_width(config) + config["index_head_dim"]))
    return CACHE_BYTES * layers(config, "F") * a_layer


def tick_bytes(config, tokens, sequences, kv_positions, touched=None):
    """Bytes a tick has to move when nothing but weights and the cache rows
    its mathematics reads and writes touch memory."""
    dense = (layers(config, "F") * (attention_params(config) + indexer_params(config))
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
