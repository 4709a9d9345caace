"""The decode tick's share of its roofline in the looped-stack cell: the least
time the chip could take for the mean decode tick (``lib/ouro_ticks.py``,
``lib/opcounts_ouro.py``: the tokens it was fed through every matrix once a
pass, the stack's weights streamed once a PASS and the head once, the fed
slots' live int8 rows once a pass a layer) over the p50 of the decode ticks'
whole ``tick`` span. An earlier output line names the bound that applies and
the weight bytes a tick streams. The span holds the host's share of the tick
too, so the share cannot pass 100 however short a program grows under an
unchanged host."""

from benchmarks.lib import ouro_ticks


def read(ctx):
    return ouro_ticks.tick_roofline_pct(ctx, "decode")
