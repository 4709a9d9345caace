"""Model FLOP/s utilization of a training cell: operations the forward and
backward passes require per token (recomputed ones not counted) x tokens
per second per chip, over the chip's published bf16 peak. The rate is that
of the steps the profiler was off in (all of them in an untraced run)."""

from benchmarks.lib import opcounts


def read(ctx):
    rate = ctx["counters"].get("train_tok_s_chip_steady")
    if rate is None or ctx["peaks"] is None:
        return None
    config, traffic = ctx["cell"].config, ctx["cell"].traffic
    vocab_rows = config["train"].get("vocab_rows", config["vocab_size"])
    n_params = opcounts.gpt2_matmul_params(config["n_embd"], config["n_layer"], vocab_rows)
    per_token = opcounts.model_flops_per_token(n_params, config["n_layer"], config["n_embd"],
                                               traffic["seq_len"], causal=True)
    return 100.0 * rate * per_token / ctx["peaks"]["bf16_flops"]
