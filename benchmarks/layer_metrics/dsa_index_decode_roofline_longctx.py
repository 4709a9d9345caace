"""The decode tick's index-score kernel at its roofline: the least time the
chip could take for every traced decode tick's ``dsa_index_decode`` calls
(``ops/pallas/sparse_index.py``; for the decode ticks the traced slice holds whole, from those ticks' own counts,
``lib/dots3_note_ticks.py`` ``traced_counts``: a full layer's kernel reads the fed slots' live
index keys once, 256 B each, pays 64 heads x 128 x 2 a query-position pair
and writes one float32 score a pair; ``lib/opcounts_dots3_note.py``) over
those kernels' device time (``pallas:dsa:index_decode``, which the family's
``op_label`` names from ``%dsa_index_decode*``). A program with no such
kernel reads nothing."""

from benchmarks.lib import dots3_note_ticks


def read(ctx):
    return dots3_note_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:index_decode", "decode", "index")
