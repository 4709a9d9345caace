"""Share of the held expert layers' row buffer that a prefill tick fills:
100 x ``moe_rows_routed_prefill`` / ``moe_rows_buffered_prefill``. Both are
counted on the device and come back with the tick's tokens: routed is the
rows of real tokens whose expert is held here, buffered the rows of the
buffer the layer chose for them from that count (``MOELayer``'s held path:
the smallest of its static sizes that fits, the largest every copy), which
is what its gathers, expert matmuls, ``relu^2`` and combine run over. A
layer that sizes its buffer for every copy reads ~5 at this cell's fill
(8,192 positions a fifth fed, a quarter of the experts held); a program
with no such counter (the parent) reads nothing. Totals of the process."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("moe_rows_routed_prefill", "moe_rows_buffered_prefill")
