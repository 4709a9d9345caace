"""Share of the rows the grouped expert matmuls visit that hold no token:
100 x (1 - ``moe_rows_routed`` / ``moe_rows_visited``), both counted on the
device in every training step and summed over the process. ``rows_visited``
are the rows of the row tiles the kernels run over
(``grouped_matmul.rows_visited``: each held expert's rows rounded up to its
tile; the backward's ``gmm`` and ``tgmm`` visit the same). None on a program
without the counters (the parent)."""

from benchmarks.lib import smallthinker_steps


def read(ctx):
    got = smallthinker_steps.counts(ctx)
    if got is None:
        return None
    return 100.0 * (1.0 - got["rows_routed"] / max(got["rows_visited"], 1))
