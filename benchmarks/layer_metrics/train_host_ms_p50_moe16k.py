"""``train_host_ms_p50`` under the SmallThinker cell's name: per
``train_batch`` span of the engine, the span less its ``device_wait`` child;
p50 over the steps the program's ring holds. Here it holds the read of the
step's device-side counts too (``_post_step``, after the timer's sync). An
entry of its own because ``tests/unit/benchmark/test_bench_program_spans.py``
holds the accepted entry's ``workloads`` to the two GPT-2 cells."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.train_host_ms_p50()
