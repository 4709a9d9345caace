"""Host time of a scheduler tick under the standing backlog: as
``sched_host_ms_p50_chat``, in the cells whose end-to-end metric is tokens
per second. Per non-idle tick, the program's ``tick`` span less its
``device_wait`` child; p50; the split by phase goes to an earlier output
line. ``lib/program_spans.py`` says which ticks are left out."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.sched_host_ms_p50()
