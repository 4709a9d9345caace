"""The prefill tick's share of its roofline: as ``decode_roofline_reason`` for
the mean prefill tick (the prompt tokens it was fed, not the slots x chunk
positions the program computes) over the p50 of the prefill ticks' whole
``tick`` span."""

from benchmarks.lib import nemotron_h_ticks


def read(ctx):
    return nemotron_h_ticks.tick_roofline_pct(ctx, "prefill")
