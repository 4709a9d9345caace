"""How full the prefill programs that ran were, in the saturated cells whose
prefill program has a rung below the whole: 100 x
``prefill_positions_fed`` / ``prefill_positions_run``, totals of the process.
``_run`` is what each prefill tick's program computed: the sequences of the
rung it ran (the smallest of the program's sizes that holds the slots the
tick feeds, ``serving/programs.py`` ``prefill_rungs``) x the chunk.
``prefill_fill_pct_sat`` beside it divides the same tokens by the cell's
whole shape, slots x chunk a tick, whatever ran: the two are equal where
every tick runs every slot, and this one is the higher by what the smaller
rungs spared. A program that counts no ``prefill_positions_run`` (the
parent: one size) reads nothing."""

from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.counter_ratio_pct("prefill_positions_fed", "prefill_positions_run")
