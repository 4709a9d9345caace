"""Ticks of the chat cell that hung: the counter ``units_stalled_tick``. The
recorder (``utils/trace.py::_unit_closed``) keeps the typical length of each
kind of tick by the rows its program ran, and counts one that ran over five
times it and over 0.25 s, with
a ``stall`` record that names the phase it hung under
(``decode:device_wait``: the device's; ``prefill:launch``: the host's); idle
ticks and ticks that compiled are none. Every other reader is a median, to
which a stall is invisible. The records are on the
``program_dispatch_split`` line. None on a program without the counter."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.units_stalled("tick")
