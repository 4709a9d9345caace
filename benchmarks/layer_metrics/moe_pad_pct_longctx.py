"""Share of the expert matmuls' rows that is padding in the long-context
cell: as ``moe_pad_pct_reason``, 100 x (1 - ``moe_rows_routed`` /
``moe_rows_computed``), both counted on the device by the held route."""

from benchmarks.lib import program_spans


def read(ctx):
    real = program_spans.counter_ratio_pct("moe_rows_routed", "moe_rows_computed")
    return None if real is None else 100.0 - real
