"""Operations and bytes of an Ouro server's ticks and of its one kernel,
computed from the published sizes and the tick's own shapes. Kept with the
benchmark so that the program cannot change its own yardstick. What the
program pads (parked slots, a short chunk) or computes twice is never counted.

The stack is LOOPED: its ``num_hidden_layers`` layers run ``total_ut_steps``
times a token over one set of weights. So every projection, MLP and norm is
counted once A PASS for the fed tokens, the stack's weights are streamed once a
pass (a tick cannot hold 4.93 GB of them on the chip between passes), each pass
of each layer reads its own int8 rows of the fed slots' live positions, and the
head runs once, on the last pass's output. The exit gate is not run at the
published ``early_exit_threshold`` of 1 and is not counted.

``config`` is the parsed configuration file (keys as published).
"""

WEIGHT_BYTES = 2          # bf16 weights, as served


def passes(config):
    return config["total_ut_steps"]


def matmul_params_per_layer(config):
    """q, k, v and o projections (no bias) and the SwiGLU's three matrices."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * h * heads * d + 2 * h * kv * d + 3 * h * config["intermediate_size"]


def layer_params(config):
    """A layer's matrices and its four RMSNorm weights (the sandwich)."""
    return matmul_params_per_layer(config) + 4 * config["hidden_size"]


def stack_params(config):
    """What one pass streams: every layer and the final norm that closes it."""
    return config["num_hidden_layers"] * layer_params(config) + config["hidden_size"]


def head_params(config):
    """The untied output head; the embedding is a lookup."""
    return config["hidden_size"] * config["vocab_size"]


def total_params(config):
    """Every parameter of the model: the table, the stack with its final norm,
    the head, and the exit gate's ``hidden -> 1`` linear with its bias."""
    return (config["vocab_size"] * config["hidden_size"] + stack_params(config)
            + head_params(config) + config["hidden_size"] + 1)


def kv_bytes_per_position(config, int8=True):
    """Bytes one cache position holds in one layer for ONE pass: keys and
    values of every KV head as int8 codes with a bf16 scale per head, or bf16."""
    heads, d = config["num_key_value_heads"], config["head_dim"]
    return 2 * heads * (d + 2) if int8 else 2 * heads * d * 2


def kv_bytes_per_token(config, int8=True):
    """Bytes a cached token holds: a row a layer A PASS."""
    return passes(config) * config["num_hidden_layers"] * kv_bytes_per_position(config, int8)


def decode_weight_bytes(config):
    """Weight bytes a decode tick streams: the stack once a pass, the head once."""
    return (passes(config) * stack_params(config) + head_params(config)) * WEIGHT_BYTES


def tick_bytes(config, tokens, positions, int8_kv=True):
    """Bytes a tick has to move when nothing but weights, the cache rows it
    attends and its own new cache rows touch memory. ``positions``: the fed
    slots' live positions, summed (what ONE walk of one layer reads)."""
    walks = passes(config) * config["num_hidden_layers"]
    return decode_weight_bytes(config) + walks * (positions + tokens) * kv_bytes_per_position(
        config, int8_kv)


def tick_flops(config, tokens, sequences, positions):
    """FLOPs a tick's mathematics needs: every matrix of every layer once a
    pass for every fed token, scores and values against the positions each
    query attends (``positions`` in all a walk, shared out evenly over the
    ``sequences``), and the head for the one position of each sequence whose
    logits are used."""
    h = config["num_attention_heads"] * config["head_dim"]
    attended = positions / max(sequences, 1)
    per_token_a_pass = config["num_hidden_layers"] * (
        2 * matmul_params_per_layer(config) + 4 * h * attended)
    return tokens * passes(config) * per_token_a_pass + sequences * 2 * head_params(config)


def pool_decode_bytes(config, positions, int8=True):
    """Bytes ONE call of the decode walk (``ops/pallas/pool_decode.py``: one
    layer, one pass) has to read: the fed slots' live positions' rows."""
    return positions * kv_bytes_per_position(config, int8)


def pool_decode_flops(config, positions):
    """FLOPs of one call: a score and a weighted value a head a live position."""
    return 4 * config["num_attention_heads"] * config["head_dim"] * positions


def roofline_ms(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"] * 1e3
    t_memory = nbytes / peaks["hbm_bytes_s"] * 1e3
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
