"""The decode program as the scheduler waits for it: p50 of the
``device_wait`` span of decode ticks, the blocking ``np.asarray(tok)``
between the dispatch and the commit loop. The device runs the program
while the host waits here, so this is the program's time less what the
dispatch overlapped. An earlier output line gives the same ticks' host
time and whole length, to hold against the runner's ``decode_tick_ms_p50``.
``lib/program_spans.py`` says which ticks are left out."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.device_wait_ms_p50("decode")
