"""The grouped expert matmuls' share of their roofline in the mixed-lengths
cell: the least time for the routed matmuls over every tick in the traced
slice (each tick at its kind's mean shape; ``lib/opcounts_laguna.py``: three
matrices an expert of 2,048 x 512, the touched experts' weights once, each
routed row in and out) over those kernels' device time (``pallas:moe:*``, as
the family's ``op_label`` names them). All 256 experts of a layer are held, so
a decode tick of 32 rows an expert-eighth touches most of them for one row
each: the share is the weights' stream's."""

from benchmarks.lib import harness, laguna_ticks, program_spans, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:moe")
    if not kernel_s or ctx["peaks"] is None:
        return None
    ticks = laguna_ticks.traced_ticks(ctx["trace"]["window_s"])
    if not ticks:
        return None
    least_s = laguna_ticks.moe_kernels_least_s(
        ctx["cell"].config, program_spans.ring()[1], ctx["counters"], ctx["peaks"], ticks)
    harness.log(moe_kernel_roofline={"traced_ticks": ticks, "kernel_s": kernel_s,
                                     "least_s": least_s})
    return 100.0 * least_s / kernel_s if least_s else None
