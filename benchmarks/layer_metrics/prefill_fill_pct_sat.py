"""How full the prefill program runs: prompt tokens put into prefill ticks
(``prefill_positions_fed``) over the positions those ticks computed
(``prefill_positions_computed``: slots x chunk a tick, whatever it is fed),
both counted by the scheduler where the tick is built. Totals of the
process: the set-up's two checked requests are in them, six ticks of a
run's four hundred."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("prefill_positions_fed", "prefill_positions_computed")
