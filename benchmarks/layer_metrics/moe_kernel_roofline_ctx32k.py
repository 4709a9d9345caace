"""The grouped expert matmuls' share of their roofline in the 32k-context cell:
as ``moe_kernel_roofline_longctx``, the least time for the routed matmuls of the
eight experts held here over every tick in the traced slice (each tick at its
kind's mean shape; ``lib/opcounts_deepseek_v32.py``: three matrices an expert,
the touched held experts' weights once, each routed row in and out) over those
kernels' device time (``pallas:moe:*``, as the family's ``op_label`` names them)."""

from benchmarks.lib import deepseek_v32_ticks, harness, program_spans, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:moe")
    if not kernel_s or ctx["peaks"] is None:
        return None
    ticks = deepseek_v32_ticks.traced_ticks(ctx["trace"]["window_s"])
    if not ticks:
        return None
    least_s = deepseek_v32_ticks.moe_kernels_least_s(
        ctx["cell"].config, program_spans.ring()[1], ctx["counters"], ctx["peaks"], ticks)
    harness.log(moe_kernel_roofline={"traced_ticks": ticks, "kernel_s": kernel_s,
                                     "least_s": least_s})
    return 100.0 * least_s / kernel_s if least_s else None
