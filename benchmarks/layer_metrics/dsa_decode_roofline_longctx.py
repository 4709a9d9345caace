"""The decode tick's selected attention kernel at its roofline: the least
time the chip could take for every traced decode tick's ``dsa_decode`` calls
(``ops/pallas/latent_decode.py`` over a selection; a full layer's kernel is
OWED the chosen latents alone, at most 2,048 a slot at 1,152 B, and 128 heads
x (2 x 512 + 64) x 2 a chosen pair; ``lib/opcounts_dots3_note.py``) over
those kernels' device time (``pallas:dsa:decode``). The kernel that runs
reads every live block of the pool and masks the unchosen columns, so past
2,048 live positions the share falls as 2,048 over the live length: that is
the read a gather would save, and the share is where it shows."""

from benchmarks.lib import dots3_note_ticks


def read(ctx):
    return dots3_note_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:decode", "decode", "decode")
