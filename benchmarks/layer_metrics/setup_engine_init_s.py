"""Objects, weights and pools: the program's ``init_inference`` and
``scheduler_init`` spans (serving) or ``initialize`` and
``initialize_state`` spans (training), less the trace, lowering and backend
compile records that fell inside them. What is left is config and
topology, placing the weights, allocating the cache, building the closures,
and the first execution of the programs made there. None on a program that
counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_engine_init_s")
