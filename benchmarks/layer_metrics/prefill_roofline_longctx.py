"""The prefill tick's share of its roofline: as ``decode_roofline_longctx`` for
the mean prefill tick, each attention owed in the cheaper of its two forms
for a chunk and over the chosen pairs alone (the program's walk computes
every live pair and masks), over the p50 of the prefill ticks' whole
``tick`` span. The walks and the selection are XLA loops the trace cannot
name: this share carries them, and ``tools/dsa_attention_time.py`` times
them alone."""

from benchmarks.lib import dots3_note_ticks


def read(ctx):
    return dots3_note_ticks.tick_roofline_pct(ctx, "prefill")
