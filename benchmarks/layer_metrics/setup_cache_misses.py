"""Programs of set-up the persistent compile cache did not answer:
backend compile events under the program's spans with no ``cache_hits``
event of their own. Counted from hits, because JAX 0.9.0 reports a miss
only where it goes on to write an entry, and programs quicker than
``jax_persistent_cache_min_compile_time_secs`` never show there. On a warm
run it is the cell's constant number of such quick programs; against an
emptied cache it equals ``setup_programs_loaded``. None on a program that
counts none."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_cache_misses")
