"""Model FLOP/s utilization of the SmallThinker training cell, the share of
the whole step: operations the forward and backward passes require per
token (``lib/opcounts_smallthinker.py``: attention over live pairs only,
causal and windowed; the expert rows ROUTED to the experts held here, as the
program counted them on the device, else the even router's share;
recomputation never counted) x tokens per second per chip of the steps the
profiler was off in, over the chip's published bf16 peak."""

from benchmarks.lib import harness, opcounts_smallthinker as ops, smallthinker_steps


def read(ctx):
    rate = ctx["counters"].get("train_tok_s_chip_steady")
    if rate is None or ctx["peaks"] is None:
        return None
    config, seq = ctx["cell"].config, ctx["cell"].traffic["seq_len"]
    rows = smallthinker_steps.rows_per_token(ctx)
    per_token = ops.train_flops_per_token(config, seq, rows)
    harness.log(train_mfu={"flops_per_token": per_token, "expert_rows_per_token_layer": rows,
                           "even_router_rows": ops.even_rows_per_token(config)})
    return 100.0 * rate * per_token / ctx["peaks"]["bf16_flops"]
