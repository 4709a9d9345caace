"""JAX's trace and lowering in set-up: the seconds of the program's
``compile_trace`` and ``compile_lower`` records under the spans of the
entry points (``init_inference``, ``scheduler_init``, ``warmup``,
``initialize``, ``initialize_state``) and the first tick or step, a function
traced inside another's trace counted once. It is the time the interpreter
is held to build programs, warm cache or cold: what programs that share a
trace would cut. Summed by the program's recorder as the events arrive
(``setup_trace_lower_us_<root>``); None on a program that counts none."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_trace_lower_s")
