"""Host time of a scheduler tick in the chat cell: per non-idle tick, the
program's ``tick`` span (``serving/scheduler.py::step``) less its
``device_wait`` child, the blocking read-back of the tick's tokens; p50.
What is left is admit, the numpy assembly, the stamps and puts, the
dispatch, the commit loop and the heartbeat, whose p50 and summed seconds
go to an earlier output line by tick kind. Read from the program's ring
after the run; ``lib/program_spans.py`` says which ticks are left out."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.sched_host_ms_p50()
