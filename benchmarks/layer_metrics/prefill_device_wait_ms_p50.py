"""The prefill program as the scheduler waits for it: p50 of the
``device_wait`` span of prefill ticks (as ``decode_device_wait_ms_p50``),
in the cells with a standing backlog, where chunked prefill does much or
most of the work. An earlier output
line gives the same ticks' host time and whole length, to hold against the
runner's ``prefill_tick_ms_p50``."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("prefill")
