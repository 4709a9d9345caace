"""The prefill tick's share of its roofline in the looped-stack cell: as
``decode_roofline_looped``, for the mean prefill tick (the prompt positions it
was fed, not the rung's sequences x chunk its program computes) over the p50
of the prefill ticks' whole ``tick`` span."""

from benchmarks.lib import ouro_ticks


def read(ctx):
    return ouro_ticks.tick_roofline_pct(ctx, "prefill")
