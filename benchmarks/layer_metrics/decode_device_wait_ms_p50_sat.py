"""The decode program as the scheduler waits for it under a standing
backlog: p50 of the ``device_wait`` span of decode ticks, the reader of
``decode_device_wait_ms_p50`` (chat, where it moves ``itl_p95_ms``) in the
cells whose end-to-end metric is tokens per second (the documents cell
does not report it)."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("decode")
