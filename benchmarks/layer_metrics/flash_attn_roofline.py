"""The flash-attention kernels' share of their roofline in a training cell:
the least time the chip could take for the forward + backward attention
the algorithm needs at the cell's shapes (causal; the scores the backward
kernel rebuilds are not counted) over the kernels' device time per step."""

from benchmarks.lib import harness, opcounts, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:attn")
    steps = ctx["counters"].get("traced_steps")
    if not kernel_s or not steps or ctx["peaks"] is None:
        return None
    config, traffic = ctx["cell"].config, ctx["cell"].traffic
    seqs, seq, heads = traffic["seqs_per_chip"], traffic["seq_len"], config["n_head"]
    head_dim = config["n_embd"] // heads
    flops = config["n_layer"] * opcounts.attention_flops(seqs, heads, seq, head_dim)
    nbytes = config["n_layer"] * opcounts.attention_bytes(seqs, heads, seq, head_dim)
    least, bound = opcounts.roofline_seconds(flops, nbytes, ctx["peaks"])
    harness.log(flash_attn_roofline_bound=bound, kernel_s_per_step=kernel_s / steps,
                least_s_per_step=least)
    return 100.0 * least / (kernel_s / steps)
