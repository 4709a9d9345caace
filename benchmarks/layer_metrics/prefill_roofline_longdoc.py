"""The prefill tick's share of its roofline: as ``decode_roofline_longdoc`` for
the mean prefill tick (the prompt tokens it was fed, not the slots x chunk
positions the program computes; the attention in its expanded form, each
attended position's keys and values made from its latent once a chunk) over
the p50 of the prefill ticks' whole ``tick`` span."""

from benchmarks.lib import joyai_llm_flash_ticks


def read(ctx):
    return joyai_llm_flash_ticks.tick_roofline_pct(ctx, "prefill")
