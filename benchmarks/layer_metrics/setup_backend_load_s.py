"""The backend's share of set-up: the seconds of the program's
``compile_backend`` records under the spans of the entry points and the
first tick or step. With a warm persistent cache that is the retrieval and
the load of each executable, with a cold one XLA's compilation; the
``program_setup_split`` line gives the retrieval alone beside it
(``cache_load_s``). Summed by the program's recorder as the events arrive
(``setup_backend_us_<root>``); None on a program that counts none."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_backend_load_s")
