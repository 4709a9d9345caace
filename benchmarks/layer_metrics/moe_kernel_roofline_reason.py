"""The grouped expert matmuls' share of their roofline: the least time the
chip could take for the routed matmuls of the experts held here over every
tick in the traced slice (each tick at its kind's mean shape;
``lib/opcounts_nemotron_h.py``: the FLOPs of the rows routed here, the
touched held experts' weights once, each routed row into and out of both
matmuls) over those kernels' device time (``pallas:moe:*``, as the family's
``op_label`` names them; the row moves are XLA gathers and are in neither).
The time is the device trace's; the rows and the experts touched are the
program's own device-side counts (``lib/nemotron_h_ticks.py``)."""

from benchmarks.lib import harness, nemotron_h_ticks, program_spans, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:moe")
    if not kernel_s or ctx["peaks"] is None:
        return None
    ticks = nemotron_h_ticks.traced_ticks(ctx["trace"]["window_s"])
    if not ticks:
        return None
    least_s = nemotron_h_ticks.moe_kernels_least_s(ctx["cell"].config, program_spans.ring()[1],
                                                   ctx["counters"], ctx["peaks"], ticks)
    harness.log(moe_kernel_roofline={"traced_ticks": ticks, "kernel_s": kernel_s,
                                     "least_s": least_s})
    return 100.0 * least_s / kernel_s if least_s else None
