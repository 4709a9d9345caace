"""The prefill tick's index-score kernel at its roofline: as
``dsa_index_decode_roofline_longctx`` for ``dsa_index_prefill`` (one call a
fed slot a full layer: a chunk's queries against that slot's live index
keys, the 64 heads walked inside the kernel; the products of every live
pair, each live key once, one float32 score a pair out) over its device
time (``pallas:dsa:index_prefill``)."""

from benchmarks.lib import dots3_note_ticks


def read(ctx):
    return dots3_note_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:index_prefill", "prefill", "index")
