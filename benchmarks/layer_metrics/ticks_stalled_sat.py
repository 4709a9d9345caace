"""Ticks that hung under a standing backlog: as ``ticks_stalled_chat``:
the counter ``units_stalled_tick``, in the cells whose end-to-end metric is
tokens per second, where one stalled tick of seconds is a run short by as
much. None on a program without the counter."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.units_stalled("tick")
