"""Operations and bytes a training step of the SmallThinker cell needs,
computed from the published sizes and the step's own shapes. Kept with the
benchmark so that the program cannot change its own yardstick. Recomputed
operations (remat, a flash backward that rebuilds the scores) and rows a
kernel pads are never counted: these are what the mathematics requires.

``config`` is the parsed configuration file (keys as published, cut as the
file says: four layers, sixteen experts held, a quarter of the vocabulary).
Attention is counted over LIVE (query, key) pairs only: ``k <= q`` and, in a
window layer, ``k > q - window``. The experts are counted over the rows
ROUTED to the experts held here (``rows_per_token``: the program's own
device-side count where a reader has it, else the even router's
``top_k x held / outputs``), whatever buffer or tiles compute them.
"""

GRAD_BYTES = ACT_BYTES = WEIGHT_BYTES = 2     # bf16 compute copies, activations, gradients


def held(config):
    return int((config.get("experts_held") or [0, config["moe_num_primary_experts"]])[1])


def router_outputs(config):
    return int(config.get("moe_num_primary_experts_published", config["moe_num_primary_experts"]))


def attention_params_per_layer(config):
    """q and o ([hidden, heads x head_dim]), k and v ([hidden, kv heads x
    head_dim]), no bias."""
    h, d = config["hidden_size"], config["head_dim"]
    return 2 * h * config["num_attention_heads"] * d + 2 * h * config["num_key_value_heads"] * d


def layer_params_outside_experts(config):
    """Attention, the router (every published output) and the two norms."""
    h = config["hidden_size"]
    return attention_params_per_layer(config) + h * router_outputs(config) + 2 * h


def expert_params(config):
    """One ReGLU expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def head_params(config):
    rows = config.get("train", {}).get("vocab_rows", config["vocab_size"])
    return rows * config["hidden_size"]


def params_held(config):
    """Every parameter the chip holds: the built tree's leaves."""
    layer = layer_params_outside_experts(config) + held(config) * expert_params(config)
    return (config["num_hidden_layers"] * layer + 2 * head_params(config)
            + config["hidden_size"])


def live_pairs(seq, window=None):
    """Live (query, key) pairs of one sequence of ``seq`` positions: query
    ``q`` sees ``min(q + 1, window)`` keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_windows(config):
    return [config["sliding_window_size"] if on else None
            for on in config["sliding_window_layout"]]


def attention_pairs(config, seq):
    """Live pairs of one sequence over all the layers."""
    return sum(live_pairs(seq, window) for window in layer_windows(config))


def even_rows_per_token(config):
    """Expert rows a token owes this chip a layer under an even router."""
    return config["moe_num_active_primary_experts"] * held(config) / router_outputs(config)


def forward_flops_per_token(config, seq, rows_per_token=None):
    """Model FLOPs of one token's forward pass: 2 a parameter of every
    matmul it goes through (projections, router, the rows routed here, the
    head; the table is a lookup) and 4 x heads x head_dim a live pair."""
    rows = even_rows_per_token(config) if rows_per_token is None else rows_per_token
    h = config["hidden_size"]
    per_layer = 2 * (attention_params_per_layer(config) + h * router_outputs(config)
                     + rows * expert_params(config))
    attn = 4 * config["num_attention_heads"] * config["head_dim"] * attention_pairs(config, seq) / seq
    return config["num_hidden_layers"] * per_layer + attn + 2 * head_params(config)


def train_flops_per_token(config, seq, rows_per_token=None):
    """Forward and backward: the backward of a matmul is two."""
    return 3.0 * forward_flops_per_token(config, seq, rows_per_token)


def flash_flops(config, seqs, seq):
    """The flash kernels' FLOPs a step, forward (QK^T, PV) and backward (dV,
    dP, dQ, dK), over live pairs."""
    per_pair = 4 * config["num_attention_heads"] * config["head_dim"]
    return 3.0 * seqs * per_pair * attention_pairs(config, seq)


def flash_bytes(config, seqs, seq):
    """Bytes they have to move a step when only operands and results touch
    memory: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv; k, v, dk, dv at the KEY heads' count."""
    d = config["head_dim"]
    q_like = seqs * seq * config["num_attention_heads"] * d * ACT_BYTES
    k_like = seqs * seq * config["num_key_value_heads"] * d * ACT_BYTES
    return config["num_hidden_layers"] * ((2 + 4) * q_like + (2 + 4) * k_like)


def moe_kernel_flops(config, rows):
    """The grouped matmuls' FLOPs for ``rows`` routed rows (all layers
    together): three products forward, and for each d lhs and d rhs."""
    return 3.0 * rows * 2 * expert_params(config)


def moe_kernel_bytes(config, rows, layer_steps):
    """Bytes at the least for ``rows`` routed rows over ``layer_steps``
    (layers x steps): a layer's held weights read once forward and once for
    d lhs, their gradient written once; each row into and out of the three
    products, forward and twice backward."""
    h, w = config["hidden_size"], config["moe_ffn_hidden_size"]
    weights = layer_steps * held(config) * expert_params(config) * (2 * WEIGHT_BYTES + GRAD_BYTES)
    per_row = (2 * (h + w) + (w + h)) * ACT_BYTES
    return weights + 3 * rows * per_row


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"]
    t_memory = nbytes / peaks["hbm_bytes_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
