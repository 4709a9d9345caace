"""What every call of the chat cell's programs is handed: the gauge
``program_operand_leaves``, the leaves of the served tree and the slot cache
(``scheduler._build``: ``tree_leaves((serve_params, cache))``), which the
jitted call flattens, checks and hands the runtime one by one each tick.
Set, not added: it is the newest scheduler's. The bytes and the host arrays
a tick adds (``program_host_operands_<program>``) are on the
``program_dispatch_split`` line. None on a program without the gauge."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.operand_leaves()
