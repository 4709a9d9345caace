"""The package's own import time: the program's ``import`` records,
written on the last line of the ``__init__`` of ``deepspeed_tpu``, its
``inference``, ``inference.serving``, ``models`` and ``ops.pallas`` packages,
and around each module the package imports at first use
(``deepspeed_tpu.initialize``), third parties they pull included, nested
ones counted once; one that falls inside a model's trace is that trace's
and is in ``setup_trace_lower_s``. Interpreter start, ``import jax`` by the
runner and the TPU client's start are not the program's and stay in the
runner's ``imports`` phase. None on a program that counts no set-up."""

from benchmarks.lib import program_setup


def read(ctx):
    return program_setup.read(ctx, "setup_import_s")
