"""The prefill tick's index-score kernel at its roofline in the 32k-context
cell: as ``dsa_index_decode_roofline_ctx32k`` for ``dsa_index_prefill`` (one call
a fed slot a layer: a chunk's queries against that slot's live index keys) over
its device time (``pallas:dsa:index_prefill``)."""

from benchmarks.lib import deepseek_v32_ticks


def read(ctx):
    return deepseek_v32_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:index_prefill", "prefill",
                                                  "index")
