"""``setup_backend_load_s`` under the long-context cell's name: the backend compile events under the same roots (``setup_backend_us_<root>``): the persistent cache's retrieval and the executables' load when warm, XLA's compilation when cold. The reader
is ``lib/program_setup.py``'s, as the four cells that report ``setup_backend_load_s`` use it;
None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_backend_load_s")
