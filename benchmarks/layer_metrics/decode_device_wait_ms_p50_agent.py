"""The decode program of the expert model as the scheduler waits for it:
p50 of the ``device_wait`` span of decode ticks (as
``decode_device_wait_ms_p50``), under the standing agent backlog."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("decode")
