"""``setup_engine_init_s`` under the long-context cell's name: the spans of ``init_inference`` and ``scheduler_init`` less the compiles inside them: objects, 8.17 GB of weights placed, the three kinds of pool allocated, the probe. The reader
is ``lib/program_setup.py``'s, as the four cells that report ``setup_engine_init_s`` use it;
None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_engine_init_s")
