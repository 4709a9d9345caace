"""``setup_programs_loaded`` under the long-context cell's name: backend compile events under the set-up roots, cache hits included (``setup_programs_loaded_<root>``). The reader
is ``lib/program_setup.py``'s, as the four cells that report ``setup_programs_loaded`` use it;
None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_programs_loaded")
