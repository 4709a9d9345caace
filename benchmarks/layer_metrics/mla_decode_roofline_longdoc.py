"""The absorbed decode step's kernel at its roofline: the least time the chip
could take for every decode tick's kernels in the traced slice (each tick at
the mean decode tick's shape, ``lib/joyai_llm_flash_ticks.py``: a layer's
kernel reads the fed slots' live latent positions once, 1,152 B each, and
pays heads x (2 x rank + rope) a query-position pair;
``lib/opcounts_joyai_llm_flash.py``) over those kernels' device time
(``pallas:mla:decode``: ``ops/pallas/latent_decode.py``'s call, which the
family's ``op_label`` names from ``%mla_decode*``). The time is the device
trace's; the live positions are the runner's count and the fed slots the
program's. A program with no such kernel (the parent, or XLA's two matmuls
over the whole pool) reads nothing.

``mla_attn_time_pct_longdoc`` is the share of device-busy time of every
latent-attention kernel (``pallas:mla:*``): the prefill walk is
one too (``pallas:mla:prefill``, ``ops/pallas/latent_walk.py``'s call
``mla_prefill_walk``) and is in that share, not in this roofline, which
reads ``pallas:mla:decode`` alone. There is no ``mla_prefill_roofline_longdoc``
yet: ``prefill_roofline_longdoc`` carries the walk, and
``tools/mla_attention_time.py`` times it alone."""

from benchmarks.lib import harness, joyai_llm_flash_ticks, program_spans, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:mla:decode")
    if not kernel_s or ctx["peaks"] is None:
        return None
    ticks = joyai_llm_flash_ticks.traced_ticks(ctx["trace"]["window_s"]).get("decode")
    if not ticks:
        return None
    least_s = joyai_llm_flash_ticks.decode_kernels_least_s(
        ctx["cell"].config, program_spans.ring()[1], ctx["counters"], ctx["peaks"], ticks)
    harness.log(mla_decode_roofline={"traced_decode_ticks": ticks, "kernel_s": kernel_s,
                                     "least_s": least_s})
    return 100.0 * least_s / kernel_s if least_s else None
