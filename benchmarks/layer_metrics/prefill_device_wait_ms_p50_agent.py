"""The prefill program of the expert model as the scheduler waits for it:
p50 of the ``device_wait`` span of prefill ticks (as
``prefill_device_wait_ms_p50``), 32 slots x 64-token chunks."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("prefill")
