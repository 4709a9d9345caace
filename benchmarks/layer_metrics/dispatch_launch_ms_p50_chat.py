"""The jitted call of a tick in the chat cell, alone: p50 of the ``launch``
span (``serving/scheduler.py::_launch``, inside ``dispatch``: the C++ fast
path's flatten of the served tree and the slot cache, the copy of the tick's
few host arrays, the runtime's enqueue) over the steady non-idle ticks that
dispatched a program. What ``sched_host_ms_p50_chat`` holds of it is the
part a change to the operands or to the order of dispatch can move. An
earlier output line (``program_dispatch_split``) splits ``dispatch`` into
``launch``, ``account`` and the rest by kind of tick, sets the calls' wall
time against their CPU time, gives this p50 by what the device was doing
under the call, and says what the ring holds. ``lib/program_dispatch.py``
says which ticks are left out. None on a program without the span."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.launch_ms_p50()
