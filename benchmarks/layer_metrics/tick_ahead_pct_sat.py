"""How often the scheduler ran a tick ahead in the cells whose backlog keeps
every slot busy:
programs dispatched while another was in flight (``ticks_dispatched_ahead``,
counted in ``scheduler.py::_dispatched``) over all programs dispatched
(``ticks_dispatched``), totals of the process, set-up's two checked requests
included. Under 100 by the programs dispatched into an empty device and by
those of a scheduler that reads every program in its own step (a drafter, the
prefix cache). Nothing on a program that counts no ``ticks_dispatched`` (the
parent, which reads every tick before it dispatches the next)."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("ticks_dispatched_ahead", "ticks_dispatched")
