"""The decode program of the indexed and windowed latent-attention model as
the scheduler waits for it: p50 of the ``device_wait`` span of decode ticks
(as ``decode_device_wait_ms_p50``), under the standing long-context backlog:
each full layer scores a slot's live index keys and attends 2,048 of its
positions, each sliding layer its ring, and the held experts that got a row
are streamed. Since PR 35 the span is the time the host was BLOCKED on the
program, its own share of the tick left out."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("decode")
