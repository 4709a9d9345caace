"""Share of the expert matmuls' rows that is padding: 100 x (1 -
``moe_rows_routed`` / ``moe_rows_computed``), both counted by the scheduler
where the tick is built: routed is positions fed x experts per token x
expert layers, computed is the rows the tick's expert matmuls are given
(parked slots, the rest of a short chunk, and whatever the buffer's layout
adds: nothing when the copies are grouped by expert, 8x at a capacity of
every token). Totals of the process, the set-up's two checked requests
among them. Tiles the grouped-matmul kernel visits twice at a group's
edge are inside the kernel and are not seen here."""

from benchmarks.lib import program_spans


def read(ctx):
    fed = program_spans.counter_ratio_pct("moe_rows_routed", "moe_rows_computed")
    return None if fed is None else 100.0 - fed
