"""The prefill program of the latent-attention model as the scheduler waits
for it: p50 of the ``device_wait`` span of prefill ticks (as
``prefill_device_wait_ms_p50``): one 512-position chunk a slot, its
attention the expanded walk over the fed slots' live key blocks."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("prefill")
