"""Share of the tokens' expert copies whose expert another chip of the
deployment holds: 100 x (1 - ``moe_rows_routed`` / ``moe_copies``), counted
on the device in every training step. With 16 of 64 experts held and an even
router it reads 75; this chip computes none of them.

The log line beside it carries the mean buffer chosen and every count a
layer (``moe_pad_pct_moe16k`` and ``moe_load_max_over_mean_moe16k`` read the
same counts)."""

from benchmarks.lib import harness, smallthinker_steps


def read(ctx):
    got = smallthinker_steps.counts(ctx)
    if got is None:
        return None
    layer_steps = got["steps"] * ctx["cell"].config["num_hidden_layers"]
    harness.log(moe_step_counts=got, moe_rows_buffered_mean=got["rows_buffered"] / layer_steps,
                moe_step_counts_by_layer=smallthinker_steps.by_layer())
    return 100.0 * (1.0 - got["rows_routed"] / got["copies"])
