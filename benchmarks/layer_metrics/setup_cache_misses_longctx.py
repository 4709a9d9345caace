"""``setup_cache_misses`` under the long-context cell's name: ``setup_programs_loaded_*`` less ``setup_cache_hits_*``: warm, the cell's constant count of programs too quick to be cached; against an empty cache, every program. The reader
is ``lib/program_setup.py``'s, as the four cells that report ``setup_cache_misses`` use it;
None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_cache_misses")
