"""The prefill program of the indexed and windowed latent-attention model as
the scheduler waits for it: p50 of the ``device_wait`` span of prefill ticks
(as ``prefill_device_wait_ms_p50``): every fed slot's chunk scored against
that slot's live index keys, walked over its live latents with each query's
own 2,048 chosen, and over its ring."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("prefill")
