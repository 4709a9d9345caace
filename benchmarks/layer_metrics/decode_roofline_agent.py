"""The decode tick's share of its roofline: the least time the chip could take for
the mean decode tick (``lib/olmoe_ticks.py``: the tokens it was fed, the
weights of the experts that many tokens touch in expectation, the attention
weights, the head, the live cache positions of the slots it fed; routed FLOPs
only) over the p50 of the decode ticks' whole ``tick`` span. An earlier output
line names the bound that applies. The span holds the host's share of the tick
too, so the share cannot pass 100 however short a program grows under an
unchanged host."""

from benchmarks.lib import olmoe_ticks


def read(ctx):
    return olmoe_ticks.tick_roofline_pct(ctx, "decode")
