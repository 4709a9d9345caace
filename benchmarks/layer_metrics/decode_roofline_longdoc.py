"""The decode tick's share of its roofline: the least time the chip could take for
the mean decode tick (``lib/joyai_llm_flash_ticks.py``: for the tokens it was
fed, the five attention projections, the absorbed attention over the fed slots'
live latent positions read once a layer, the held experts that got a row and
the rows routed here, the router and shared matmuls, the dense layer, the
head's slice) over the p50 of the decode ticks' whole ``tick`` span. Experts
touched and rows routed are the program's own device-side counts by kind of
tick; the sizes, the arithmetic and the peaks are the benchmark's. The span
holds the host's share of the tick too, so the share cannot pass 100 however
short a program grows under an unchanged host."""

from benchmarks.lib import joyai_llm_flash_ticks


def read(ctx):
    return joyai_llm_flash_ticks.tick_roofline_pct(ctx, "decode")
