"""The decode program of the latent-attention model as the scheduler waits
for it: p50 of the ``device_wait`` span of decode ticks (as
``decode_device_wait_ms_p50``), under the standing long-document backlog: the
absorbed step reads every slot's whole latent pool, 24 x 16,384 positions a
layer, and the held experts that got a row are streamed."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("decode")
