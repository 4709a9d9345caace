"""The fullest held expert's rows over the held experts' mean, a layer a
step, averaged over layers and steps: ``moe_load_max`` x experts held /
``moe_rows_routed``, both counted on the device. 1 is an even router; the
grouped matmuls' row tiles and the deployment's slowest chip follow the
fullest. None on a program without the counters (the parent)."""

from benchmarks.lib import opcounts_smallthinker as ops, smallthinker_steps


def read(ctx):
    got = smallthinker_steps.counts(ctx)
    if got is None:
        return None
    return got["load_max"] * ops.held(ctx["cell"].config) / max(got["rows_routed"], 1)
