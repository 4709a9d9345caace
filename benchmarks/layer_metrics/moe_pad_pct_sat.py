"""Share of the expert matmuls' rows that is padding: 100 x (1 -
``moe_rows_routed`` / ``moe_rows_computed``). Both are counted on the
device, where the layer decides which of a token's experts are held here,
and come back with the tick's tokens: routed is the rows of real tokens
whose expert is held here, computed the rows of the row tiles the grouped
matmuls run over (``ops/pallas/grouped_matmul.py`` ``rows_visited``: each
group rounded out to tiles, a tile two groups share counted for each).
Parked slots and a chunk's padding route nowhere and add no row. Totals of
the process."""

from benchmarks.lib import program_spans


def read(ctx):
    real = program_spans.counter_ratio_pct("moe_rows_routed", "moe_rows_computed")
    return None if real is None else 100.0 - real
