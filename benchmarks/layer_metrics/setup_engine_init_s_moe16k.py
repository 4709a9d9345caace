"""``setup_engine_init_s`` under the SmallThinker cell's name: the ``initialize`` and ``initialize_state`` spans less their own compiles (``setup_span_us_<root>``): the plan, the seeded draw of 656.5 M float32 masters and the optimizer's moments.
The reader is ``lib/program_setup.py``'s, as the four cells that report
``setup_engine_init_s`` use it (an entry of its own because
``tests/unit/benchmark/test_bench_program_setup.py`` holds the accepted
entry's ``workloads``); None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_engine_init_s")
