"""The flash kernels' share of their roofline in the SmallThinker cell: the
least time the chip could take for the forward + backward attention of the
four layers over LIVE pairs only (causal, and inside the window on the three
window layers; ``lib/opcounts_smallthinker.py``; the scores the backward
rebuilds and the forward a remat repeats are not counted) over the kernels'
device time per step (``pallas:flash:fwd|dq|dkv`` of the family's
``op_label``)."""

from benchmarks.lib import harness, opcounts_smallthinker as ops, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:flash")
    steps = ctx["counters"].get("traced_steps")
    if not kernel_s or not steps or ctx["peaks"] is None:
        return None
    config, traffic = ctx["cell"].config, ctx["cell"].traffic
    seqs, seq = traffic["seqs_per_chip"], traffic["seq_len"]
    least, bound = ops.roofline_seconds(ops.flash_flops(config, seqs, seq),
                                        ops.flash_bytes(config, seqs, seq), ctx["peaks"])
    harness.log(flash_attn_roofline_moe16k={"bound": bound, "kernel_s_per_step": kernel_s / steps,
                                            "least_s_per_step": least})
    return 100.0 * least / (kernel_s / steps)
