"""The decode tick's share of its roofline in the mixed-lengths cell: the least
time the chip could take for the mean decode tick (``lib/laguna_ticks.py``,
``lib/opcounts_laguna.py``: for the tokens it was fed, every projection over
the layer's own heads, the gate, the full layers' scores and values over the
fed slots' live positions and their int8 rows read once, the sliding layers'
over their windows, the experts that got a row streamed once and the rows
routed, router, shared expert, dense layer, the head) over the p50 of the
decode ticks' whole ``tick`` span. The program's walk reads every slot's pool
as far as the longest slot goes and a ring whole: both are owed less than they
do, and the share says so. The span holds the host's share of the tick too, so
the share cannot pass 100 however short a program grows under an unchanged
host."""

from benchmarks.lib import laguna_ticks


def read(ctx):
    return laguna_ticks.tick_roofline_pct(ctx, "decode")
