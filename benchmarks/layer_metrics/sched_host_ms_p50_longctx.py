"""Host time of a scheduler tick under the long-context backlog: as
``sched_host_ms_p50_sat``, the program's ``tick`` span less its
``device_wait`` child per non-idle tick; p50. Five layers make the host's
share of a tick larger than a deployment's 46 would."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.sched_host_ms_p50()
