"""Operations and bytes the algorithms need, computed from shapes. Kept
with the benchmark so that the program cannot change its own yardstick.
Recomputed operations (remat, a flash backward that rebuilds the scores)
are never counted: these are what the mathematics requires."""


def gpt2_matmul_params(n_embd, n_layer, vocab_rows):
    """Parameters that take part in a matrix multiplication for every
    token: the blocks (attention 4E^2 + 4E, MLP 8E^2 + 5E, two LayerNorms
    4E) and the tied output head (vocab_rows x E, counted once). The
    position table is a lookup and is left out."""
    per_block = 12 * n_embd * n_embd + 13 * n_embd
    return n_layer * per_block + vocab_rows * n_embd + 2 * n_embd


def model_flops_per_token(n_params, n_layers=0, hidden=0, seq=0, causal=True):
    """Training FLOPs per token: 6N for the parameter matmuls plus the
    attention-score term 6N omits. Per layer QK^T and PV cost 4*seq*hidden
    FLOPs per token forward, three times that forward + backward; a causal
    mask halves it. (Copied from tools/bench_core.py, PR 23.)"""
    attn = 12.0 * n_layers * hidden * seq
    if causal:
        attn /= 2.0
    return 6.0 * n_params + attn


def attention_flops(batch, heads, seq, head_dim, causal=True, backward=True):
    """FLOPs of scaled-dot-product attention over ``batch`` sequences:
    forward is QK^T and PV (2 matmuls of 2*seq*seq*head_dim each per head);
    backward is dV, dP, dQ, dK (4 of them). Causal halves all of it."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim
    total = fwd * (3.0 if backward else 1.0)
    return total / 2.0 if causal else total


def attention_bytes(batch, heads, seq, head_dim, itemsize=2, backward=True):
    """Bytes attention has to move when nothing but its operands and
    results touch memory: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = batch * heads * seq * head_dim * itemsize
    return tensor * (4 + (8 if backward else 0))


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which limit sets it."""
    t_compute = flops / peaks["bf16_flops"]
    t_memory = nbytes / peaks["hbm_bytes_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")
