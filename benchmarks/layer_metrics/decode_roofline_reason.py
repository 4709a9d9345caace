"""The decode tick's share of its roofline: the least time the chip could take for
the mean decode tick (``lib/nemotron_h_ticks.py``: for the tokens it was fed,
the Mamba projections, convolution and recurrence, the fed slots' recurrent
state read and written once, the held experts that got a row and the rows
routed here, the router, latent and shared matmuls, attention over the live
cache positions, the head's slice) over the p50 of the decode ticks' whole
``tick`` span. Experts touched and rows routed are **the program's own
device-side counts** by kind of tick (the span and the tokens fed are its
recorder's too); the sizes, the arithmetic and the peaks are the benchmark's,
and an earlier output line gives the count beside the even router's expectation
and names the bound that applies. The span holds the host's share of the tick
too, so the share cannot pass 100 however short a program grows under an
unchanged host."""

from benchmarks.lib import nemotron_h_ticks


def read(ctx):
    return nemotron_h_ticks.tick_roofline_pct(ctx, "decode")
