"""``setup_import_s`` under the long-context cell's name: the package's own ``import`` records (``setup_import_us``), nested ones counted once. The reader
is ``lib/program_setup.py``'s, as the four cells that report ``setup_import_s`` use it;
None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_import_s")
