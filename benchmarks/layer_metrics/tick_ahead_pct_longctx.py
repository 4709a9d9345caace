"""How often the scheduler ran a tick ahead in the long-context cell: as
``tick_ahead_pct_reason``, programs dispatched while another was in flight
(``ticks_dispatched_ahead``) over all programs dispatched
(``ticks_dispatched``), totals of the process, set-up's two checked requests
included. Nothing on a program that counts no ``ticks_dispatched``."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("ticks_dispatched_ahead", "ticks_dispatched")
