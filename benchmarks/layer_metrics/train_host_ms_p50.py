"""Host time of a training step: per ``train_batch`` span of the engine
(``runtime/engine.py``), the span less its ``device_wait`` child, which
goes around the throughput timer's closing sync; p50 over every step the
program's ring holds (warm-up and the traced steps are a few of some two
hundred). What is left is the timer's opening sync, batch staging, the
dispatch and ``_post_step``; their p50 and summed seconds go to an earlier
output line."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.train_host_ms_p50()
