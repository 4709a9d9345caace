"""What every call of a saturated cell's programs is handed: as
``program_operand_leaves_chat``: the gauge ``program_operand_leaves``, the
leaves of the served tree and the slot cache (a pool a layer, a looped
stack's a pass). None on a program without the gauge."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.operand_leaves()
