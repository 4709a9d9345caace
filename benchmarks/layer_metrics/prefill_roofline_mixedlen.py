"""The prefill tick's share of its roofline in the mixed-lengths cell: as
``decode_roofline_mixedlen`` for the mean prefill tick (the chunks it was fed:
every real token's projections, routed rows and shared expert, each query's
scores over the positions at or before it on full layers and inside its window
on sliding ones, the head once a sequence) over the p50 of the prefill ticks'
whole ``tick`` span. What the fixed-shape program computes for padding and for
sequences that only fill its rung is not owed."""

from benchmarks.lib import laguna_ticks


def read(ctx):
    return laguna_ticks.tick_roofline_pct(ctx, "prefill")
