"""Operations and bytes one tick of a Laguna server needs, computed from the
published sizes and the tick's own shapes. Kept with the benchmark so that
the program cannot change its own yardstick. What the program pads (parked
slots, a short chunk), reads past what its queries attend (a ring's other
half, a pool's blocks past a slot's length) or computes twice is never counted.

``config`` is the parsed configuration file (keys as published): the heads by
layer are ``num_attention_heads_per_layer``, the kinds of layer ``layer_types``
and ``mlp_layer_types``. A tick is ``tokens`` positions of ``sequences``
sequences fed to one forward pass; what its attention has to read is given a
layer: ``full_positions`` cache positions of a full layer (the fed sequences'
live lengths, summed) and ``window_positions`` of a sliding one (of each, what
lies inside some query's window).
"""

WEIGHT_BYTES = 2          # bf16 weights, as served


def layers(config, kind):
    """How many layers are full ("F") or sliding ("W") attention, dense ("D")
    or sparse ("E") feed-forward."""
    if kind in "FW":
        want = "full_attention" if kind == "F" else "sliding_attention"
        return sum(1 for t in config["layer_types"] if t == want)
    want = "dense" if kind == "D" else "sparse"
    return sum(1 for t in config["mlp_layer_types"] if t == want)


def attention_params(config, layer):
    """One layer's attention: q and o over its own heads, k and v over the key
    heads, the gate a head, and the block's two RMSNorm weights."""
    e, d = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads_per_layer"][layer]
    gate = e * heads if config["gating"] else 0
    return 2 * e * heads * d + 2 * e * config["num_key_value_heads"] * d + gate + 2 * e


def dense_ffn_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_params(config):
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config):
    return 3 * config["hidden_size"] * config["shared_expert_intermediate_size"]


def router_params(config):
    return config["hidden_size"] * config["num_experts"]


def head_params(config):
    """The untied output head and the final norm; the table is a lookup."""
    return config["hidden_size"] * config["vocab_size"] + config["hidden_size"]


def params(config):
    """Every parameter the configuration holds."""
    n = config["num_hidden_layers"]
    sparse = config["num_experts"] * expert_params(config) + shared_params(config) \
        + router_params(config)
    return (sum(attention_params(config, i) for i in range(n))
            + layers(config, "D") * dense_ffn_params(config) + layers(config, "E") * sparse
            + head_params(config) + config["vocab_size"] * config["hidden_size"])


def experts_touched(config, tokens):
    """Expected number of distinct experts of one layer that ``tokens`` tokens
    reach when each takes ``num_experts_per_tok`` distinct experts uniformly:
    E (1 - (1 - k/E)^tokens). A seeded router is less even, so this is the
    most a tick can be asked to stream."""
    e, k = config["num_experts"], config["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def kv_bytes_per_position(config, int8=True):
    """Bytes one cache position holds in one layer, full or sliding: keys and
    values of the 8 key heads as int8 codes with a bf16 scale a head (2,080),
    or as bf16."""
    heads, d = config["num_key_value_heads"], config["head_dim"]
    return 2 * heads * (d + 2) if int8 else 2 * heads * d * 2


def expert_flops(config, tokens, rows=None):
    """FLOPs of the routed expert matmuls: ``rows`` expert rows (all sparse
    layers; None: ``num_experts_per_tok`` a token a layer)."""
    if rows is None:
        rows = tokens * config["num_experts_per_tok"] * layers(config, "E")
    return rows * 2 * expert_params(config)


def expert_bytes(config, tokens, touched=None):
    """Bytes of routed experts' weights a tick streams over the sparse layers:
    ``touched`` experts a layer (None: an even router's)."""
    if touched is None:
        touched = experts_touched(config, tokens)
    return layers(config, "E") * touched * expert_params(config) * WEIGHT_BYTES


def moe_kernel_bytes(config, tokens, touched=None, rows=None):
    """Bytes the grouped expert matmuls move at the least: the touched
    experts' weights once, and each routed row in and out of the three."""
    if rows is None:
        rows = tokens * config["num_experts_per_tok"] * layers(config, "E")
    e, w = config["hidden_size"], config["moe_intermediate_size"]
    return expert_bytes(config, tokens, touched) + rows * (2 * (e + w) + (w + e)) * WEIGHT_BYTES


def attention_pairs(config, tokens, sequences, full_positions, window_positions):
    """Query-key pairs of one full and of one sliding layer: a sequence's
    ``c = tokens / sequences`` queries end at its live length, so each sees on
    average that length less half the chunk; a sliding layer's at most the
    window. ``(full, window)``."""
    if not sequences:
        return 0.0, 0.0
    c = tokens / sequences
    full = tokens * max(full_positions / sequences - (c - 1) / 2, 1.0)
    return full, min(full, tokens * config["sliding_window"])


def tick_flops(config, tokens, sequences, full_positions, window_positions, rows=None):
    """FLOPs a tick's mathematics needs: every projection, the gate, the router
    and the shared expert of every token, the routed rows, scores and values of
    each query against the positions it attends, and the head for the one
    position a sequence whose logits are used."""
    n = config["num_hidden_layers"]
    e, d = config["hidden_size"], config["head_dim"]
    per_token = sum(attention_params(config, i) - 2 * e for i in range(n)) \
        + layers(config, "D") * dense_ffn_params(config) \
        + layers(config, "E") * (router_params(config) + shared_params(config))
    full, window = attention_pairs(config, tokens, sequences, full_positions, window_positions)
    heads = config["num_attention_heads_per_layer"]
    attn = sum(4 * d * heads[i] * (window if kind == "sliding_attention" else full)
               for i, kind in enumerate(config["layer_types"]))
    return (2 * tokens * per_token + expert_flops(config, tokens, rows) + attn
            + sequences * 2 * head_params(config))


def tick_bytes(config, tokens, sequences, full_positions, window_positions, int8_kv=True,
               touched=None):
    """Bytes a tick has to move when nothing but weights, the cache positions
    its queries attend, its own new cache rows and its tokens' table rows touch
    memory."""
    n = config["num_hidden_layers"]
    fixed = sum(attention_params(config, i) for i in range(n)) \
        + layers(config, "D") * dense_ffn_params(config) \
        + layers(config, "E") * (router_params(config) + shared_params(config)) \
        + head_params(config)
    kv = kv_bytes_per_position(config, int8_kv) * (
        layers(config, "F") * full_positions + layers(config, "W") * window_positions + n * tokens)
    return (fixed + tokens * config["hidden_size"]) * WEIGHT_BYTES \
        + expert_bytes(config, tokens, touched) + kv


def roofline_ms(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"] * 1e3
    t_memory = nbytes / peaks["hbm_bytes_s"] * 1e3
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
