"""The share of device-busy time inside the indexed attention's kernels in the
32k-context cell, all six layers: the index scores of both kinds of tick, the
selection, the prefill walk and the decode step over the chosen
(``pallas:dsa:*``, as ``families/deepseek_v32.py`` labels them: this family names
the selection kernel too, which dots3-note's label leaves among the other
kernels). The split by kernel goes to an earlier line: it is where this cell's
time goes, kernel by kernel."""

from benchmarks.lib import harness, reducers


def read(ctx):
    share = reducers.op_time_pct(ctx, "^pallas:dsa:")
    if share is None:
        return None
    harness.log(dsa_kernel_seconds={name: secs for name, secs in
                                    ctx["trace"]["family_seconds"].items()
                                    if name.startswith("pallas:")},
                busy_s=ctx["trace"]["busy_s_first"])
    return share
