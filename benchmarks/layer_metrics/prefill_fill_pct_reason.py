"""How full the prefill program runs in the reasoning cell: as
``prefill_fill_pct_sat``, prompt tokens fed over slots x chunk positions
computed, totals of the process."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("prefill_positions_fed", "prefill_positions_computed")
