"""``setup_backend_load_s`` under the SmallThinker cell's name: the backend compile events under the set-up roots and the first step (``setup_backend_us_<root>``): the persistent cache's retrieval and the executables' load when warm, XLA's compilation of the fused step when cold.
The reader is ``lib/program_setup.py``'s, as the four cells that report
``setup_backend_load_s`` use it (an entry of its own because
``tests/unit/benchmark/test_bench_program_setup.py`` holds the accepted
entry's ``workloads``); None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_backend_load_s")
