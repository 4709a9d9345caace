"""The grouped expert matmuls' share of their roofline in the SmallThinker
cell: the least time the chip could take for the three products of the rows
ROUTED to the held experts, forward and both backward products of each
(``gmm`` for d lhs, ``tgmm`` for d rhs; the forward a remat repeats is not
counted), the held weights read and their gradient written
(``lib/opcounts_smallthinker.py``), over those kernels' device time
(``pallas:moe:matmul``). The rows are the program's device-side count of a
mean step, times the traced steps."""

from benchmarks.lib import harness, opcounts_smallthinker as ops, reducers, smallthinker_steps


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:moe")
    steps = ctx["counters"].get("traced_steps")
    got = smallthinker_steps.counts(ctx)
    if not kernel_s or not steps or got is None or ctx["peaks"] is None:
        return None
    config = ctx["cell"].config
    rows = got["rows_routed"] / got["steps"] * steps
    least, bound = ops.roofline_seconds(
        ops.moe_kernel_flops(config, rows),
        ops.moe_kernel_bytes(config, rows, steps * config["num_hidden_layers"]), ctx["peaks"])
    harness.log(moe_kernel_roofline_moe16k={"bound": bound, "kernel_s": kernel_s, "least_s": least,
                                            "rows_routed_per_step": got["rows_routed"] / got["steps"]})
    return 100.0 * least / kernel_s
