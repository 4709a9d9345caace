"""The jitted call of a tick under a standing backlog, alone: as
``dispatch_launch_ms_p50_chat``, in the cells whose end-to-end metric is
tokens per second. p50 of the ``launch`` span inside ``dispatch`` over the
steady non-idle ticks that dispatched a program; the
``program_dispatch_split`` line goes to an earlier output line.
``lib/program_dispatch.py`` says which ticks are left out. None on a
program without the span."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.launch_ms_p50()
