"""``setup_trace_lower_s`` under the long-context cell's name: JAX's trace and lowering under the entry points' spans and the first tick (``setup_trace_lower_us_<root>``): the time the interpreter is held to build this cell's programs, two index kernels, the walk and the selected decode step among them. The reader
is ``lib/program_setup.py``'s, as the four cells that report ``setup_trace_lower_s`` use it;
None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_trace_lower_s")
