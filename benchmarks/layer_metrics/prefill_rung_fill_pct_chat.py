"""How full the prefill programs that ran were in the chat cell: as
``prefill_rung_fill_pct_sat``, prompt tokens fed over the positions the
rungs that ran computed (``prefill_positions_run``), totals of the process;
nothing on a program that counts no such positions (the parent)."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("prefill_positions_fed", "prefill_positions_run")
