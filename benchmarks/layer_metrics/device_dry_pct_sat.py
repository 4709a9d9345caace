"""How long the scheduler kept the chip waiting under a standing backlog:
as ``device_dry_pct_chat``: 100 x the seconds of the ``device_dry``
records (the lower bound) inside the steady non-idle ticks over those
ticks' seconds; to hold against ``device_idle_pct_sat`` of the trace. None
on a program that does not look."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.device_dry_pct()
