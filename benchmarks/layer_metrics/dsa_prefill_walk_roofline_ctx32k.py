"""The prefill tick's selected attention kernel at its roofline in the
32k-context cell: the least time for every traced prefill tick's
``dsa_prefill_walk`` calls (one call a fed slot a layer; a layer is OWED the
expanded attention of the chosen pairs alone, 128 heads x (128 + 64 + 128) x 2 a
pair, and the keys and values of the positions some query of the chunk chose,
made from their latents once, 512 x 128 x 256 x 2 each;
``lib/opcounts_deepseek_v32.py``) over those kernels' device time
(``pallas:dsa:prefill_walk``)."""

from benchmarks.lib import deepseek_v32_ticks


def read(ctx):
    return deepseek_v32_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:prefill_walk", "prefill",
                                                  "walk")
