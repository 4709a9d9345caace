"""Host time of a scheduler tick under the long-document backlog: as
``sched_host_ms_p50_sat``, the program's ``tick`` span less its
``device_wait`` child per non-idle tick; p50; the split by phase goes to an
earlier output line."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.sched_host_ms_p50()
