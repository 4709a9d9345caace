"""Programs built in set-up: backend compile events under the spans of
the entry points and the first tick or step, cache hits included. One more
rung of a serving program adds exactly one. Counted by the program's
recorder as the events arrive (``setup_programs_loaded_<root>``); None on a
program that counts none."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_programs_loaded")
