"""How long an admitted request takes to its first token: p90 of the
program's ``prefill_wait`` records, admission to the tick that sampled the
first token: its prompt's chunks, one per prefill tick, and the decode
ticks that interleave with them. The same requests as ``queue_wait_p90_ms``
(``lib/program_spans.py``), those whose first token came before the
profiler started."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.request_wait_ms("prefill_wait", 90)
