"""Training steps that hung: the counter ``units_stalled_train_batch``, kept
by the same rule as a tick's (``utils/trace.py::_unit_closed``: over five
times the typical ``train_batch`` and over 0.25 s; a step that compiled is
none). The ``stall`` records the ring holds, each with the phase the step
hung under (``train_batch:timer_sync``, ``train_batch:device_wait``), and the
ring's state go to an earlier output line (``program_stalls``). None on a
program without the counter."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.units_stalled("train_batch")
