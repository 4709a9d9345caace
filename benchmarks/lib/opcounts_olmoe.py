"""Operations and bytes one tick of an OLMoE server needs, computed from
the published sizes and the tick's own shapes. Kept with the benchmark so
that the program cannot change its own yardstick. What the program pads
(parked slots, a short chunk) or computes twice is never counted.

``config`` is the parsed configuration file (keys as published); a tick is
``tokens`` positions fed to one forward pass, ``kv_positions`` cache
positions that forward pass has to read.
"""

WEIGHT_BYTES = 2          # bf16 weights, as served


def expert_params_per_layer(config):
    """Parameters of one layer's experts: gate, up and down of each."""
    return config["num_experts"] * 3 * config["hidden_size"] * config["intermediate_size"]


def attention_params_per_layer(config):
    """q, k, v and o projections (no bias), the two QK-norm weights and the
    block's two RMSNorm weights."""
    h = config["hidden_size"]
    kv = h // config["num_attention_heads"] * config["num_key_value_heads"]
    return 2 * h * h + 2 * h * kv + (h + kv) + 2 * h


def router_params_per_layer(config):
    return config["hidden_size"] * config["num_experts"]


def head_params(config):
    """The untied output head and the final norm; the embedding is a lookup."""
    return config["hidden_size"] * config["vocab_size"] + config["hidden_size"]


def experts_touched(config, tokens):
    """Expected number of distinct experts of one layer that ``tokens``
    tokens reach when each takes ``num_experts_per_tok`` distinct experts
    uniformly: E (1 - (1 - k/E)^tokens). Trained routers are less even, so
    this is the most a tick can be asked to stream."""
    e, k = config["num_experts"], config["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def kv_bytes_per_position(config, int8=True):
    """Bytes one cache position holds in one layer: keys and values of
    every KV head as int8 codes with a bf16 scale per head, or as bf16."""
    heads = config["num_key_value_heads"]
    head_dim = config["hidden_size"] // config["num_attention_heads"]
    return 2 * heads * (head_dim * 1 + 2) if int8 else 2 * heads * head_dim * 2


def expert_bytes(config, tokens):
    """Bytes of expert weights a tick streams over all layers."""
    share = experts_touched(config, tokens) / config["num_experts"]
    return config["num_hidden_layers"] * expert_params_per_layer(config) * WEIGHT_BYTES * share


def expert_flops(config, tokens):
    """FLOPs of the routed expert matmuls only: k experts a token a layer."""
    per_token = (config["num_experts_per_tok"] * 3 * 2 * config["hidden_size"]
                 * config["intermediate_size"])
    return config["num_hidden_layers"] * tokens * per_token


def tick_bytes(config, tokens, kv_positions, int8_kv=True):
    """Bytes a tick has to move when nothing but weights, the cache
    positions it attends and its own new cache rows touch memory."""
    layers = config["num_hidden_layers"]
    dense = layers * (attention_params_per_layer(config) + router_params_per_layer(config))
    kv = layers * (kv_positions + tokens) * kv_bytes_per_position(config, int8_kv)
    return expert_bytes(config, tokens) + (dense + head_params(config)) * WEIGHT_BYTES + kv


def tick_flops(config, tokens, kv_positions, sequences):
    """FLOPs a tick's mathematics needs: the projections and the routed
    experts of every token, scores and values against the cache positions
    each query attends (``kv_positions`` in all, shared out evenly over the
    ``sequences`` the tokens belong to), and the head for the one position
    of each sequence whose logits are used."""
    layers, h = config["num_hidden_layers"], config["hidden_size"]
    proj = 2 * (attention_params_per_layer(config) + router_params_per_layer(config))
    attended = kv_positions / max(sequences, 1)
    attn = 4 * h * attended
    per_token = layers * (proj + attn) + expert_flops(config, 1)
    return tokens * per_token + sequences * 2 * head_params(config)


def moe_kernel_bytes(config, tokens):
    """Bytes the expert layer's kernels move at the least: the touched
    experts' weights once, and each routed row in and out of the two
    permutations and the three matmuls."""
    rows = tokens * config["num_experts_per_tok"]
    h, w = config["hidden_size"], config["intermediate_size"]
    permutes = 2 * rows * 2 * h * WEIGHT_BYTES             # dispatch, combine: read + write
    matmuls = rows * (2 * (h + w) + (w + h)) * WEIGHT_BYTES  # gate, up: h in, w out; down
    return expert_bytes(config, tokens) + config["num_hidden_layers"] * (permutes + matmuls)


def roofline_ms(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"] * 1e3
    t_memory = nbytes / peaks["hbm_bytes_s"] * 1e3
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
