"""The decode tick's share of its roofline: the least time the chip could take for
the mean decode tick (``lib/dots3_note_ticks.py``,
``lib/opcounts_dots3_note.py``: for the tokens it was fed, every projection,
the index scores of every live pair and the fed slots' live index keys read
once a full layer, the absorbed attention of the CHOSEN pairs and their latents
alone, the window layers' attention over their windows, the held experts that
got a row and the rows routed here, router, shared expert, dense layer, the
head's slice) over the p50 of the decode ticks' whole ``tick`` span. The
program's kernel reads every live latent and masks the unchosen, and XLA's
window step reads the whole ring: both are owed less than they do, and the
share says so. The span holds the host's share of the tick too, so the share
cannot pass 100 however short a program grows under an unchanged host."""

from benchmarks.lib import dots3_note_ticks


def read(ctx):
    return dots3_note_ticks.tick_roofline_pct(ctx, "decode")
