"""The prefill tick's selected attention kernel at its roofline: the least
time the chip could take for every traced prefill tick's ``dsa_prefill_walk``
calls (``ops/pallas/latent_walk.py``, one call a fed slot a full layer; a
layer is OWED the expanded attention of the chosen pairs alone, 128 heads x
(128 + 64 + 128) x 2 a pair, and the keys and values of the positions some
query of the chunk chose, made from their latents once, 512 x 128 x 256 x 2
each; ``lib/opcounts_dots3_note.py``) over those kernels' device time
(``pallas:dsa:prefill_walk``). The kernel that runs expands and scores every
live block and masks what a query did not choose, so the share falls with
the live length as the decode kernel's does."""

from benchmarks.lib import dots3_note_ticks


def read(ctx):
    return dots3_note_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:prefill_walk", "prefill", "walk")
