"""The decode tick's index-score kernel at its roofline in the 32k-context cell:
as ``dsa_index_decode_roofline_longctx`` with six indexed layers: the least time
for every traced decode tick's ``dsa_index_decode`` calls (the fed slots' live
index keys once, 256 B each, 64 heads x 128 x 2 a query-position pair, one
float32 score a pair out; from those ticks' own counts,
``lib/deepseek_v32_ticks.py``) over those kernels' device time
(``pallas:dsa:index_decode``). A program with no such kernel reads nothing."""

from benchmarks.lib import deepseek_v32_ticks


def read(ctx):
    return deepseek_v32_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:index_decode", "decode",
                                                  "index")
