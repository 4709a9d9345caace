"""The decode tick's selected attention kernel at its roofline in the
32k-context cell: the least time for every traced decode tick's ``dsa_decode``
calls (a layer's kernel is OWED the chosen latents alone, at most 2,048 a slot
at 1,152 B, and 128 heads x (2 x 512 + 64) x 2 a chosen pair;
``lib/opcounts_deepseek_v32.py``) over those kernels' device time
(``pallas:dsa:decode``). The kernel that runs reads every live block of the pool
and masks the unchosen columns, so at ~25,000 live positions the share is near
2,048 / 25,000 of what the read alone allows: the read a gather of the chosen
rows would save, in six layers of six."""

from benchmarks.lib import deepseek_v32_ticks


def read(ctx):
    return deepseek_v32_ticks.kernel_roofline_pct(ctx, "^pallas:dsa:decode", "decode", "decode")
