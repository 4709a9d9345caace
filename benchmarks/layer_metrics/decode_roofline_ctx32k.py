"""The decode tick's share of its roofline in the 32k-context cell: the least
time the chip could take for the mean decode tick (``lib/deepseek_v32_ticks.py``,
``lib/opcounts_deepseek_v32.py``: for the tokens it was fed, every projection,
the index scores of every live pair and the fed slots' live index keys read once
a layer, the absorbed attention of the CHOSEN pairs and their latents alone, the
held experts that got a row and the rows routed here, router, shared expert,
dense layer, the head's slice) over the p50 of the decode ticks' whole ``tick``
span. The program's kernel reads every live latent and masks the unchosen: it is
owed less than it does, in all six layers, and the share says so. The span holds
the host's share of the tick too, so the share cannot pass 100 however short a
program grows under an unchanged host."""

from benchmarks.lib import deepseek_v32_ticks


def read(ctx):
    return deepseek_v32_ticks.tick_roofline_pct(ctx, "decode")
