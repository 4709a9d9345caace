"""How full the prefill program runs in the long-context cell: as
``prefill_fill_pct_sat``, prompt tokens fed over slots x chunk positions
computed, totals of the process. The projections and the expert layers run
over every computed position; the attention's walks over the fed slots alone."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("prefill_positions_fed", "prefill_positions_computed")
