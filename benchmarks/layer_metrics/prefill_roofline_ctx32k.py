"""The prefill tick's share of its roofline in the 32k-context cell: as
``decode_roofline_ctx32k`` for the mean prefill tick, each attention owed in the
cheaper of its two forms for a chunk and over the chosen pairs alone (the
program's walk expands and scores every live block and masks), over the p50 of
the prefill ticks' whole ``tick`` span."""

from benchmarks.lib import deepseek_v32_ticks


def read(ctx):
    return deepseek_v32_ticks.tick_roofline_pct(ctx, "prefill")
