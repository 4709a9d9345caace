"""The prefill tick's share of its roofline: as ``decode_roofline_agent``, for
the mean prefill tick (the prompt positions it was fed, not the slots x
chunk its program computes) over the p50 of the prefill ticks' whole
``tick`` span. An earlier output line names the bound that applies."""

from benchmarks.lib import olmoe_ticks


def read(ctx):
    return olmoe_ticks.tick_roofline_pct(ctx, "prefill")
