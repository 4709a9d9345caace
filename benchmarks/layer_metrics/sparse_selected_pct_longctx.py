"""How sparse the full layers' attention is: 100 x positions attended over
positions live, summed over the real queries of every full layer, both kinds
of tick (``dsa_positions_selected_*`` / ``dsa_positions_live_*``: a query at
position ``t`` attends ``min(t + 1, index_topk)`` of ``t + 1``; counted on
the device from the tick's write positions and lengths and read back behind
its tokens as ``moe_rows_*`` are). Lower is sparser: 2,048 of a mean ~16,000
live is ~13; a program with no indexer (every position attended) has no such
counter and reads nothing. The split by kind goes to an earlier line."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    chosen, live = (sum(counters.get(f"dsa_positions_{what}_{kind}", 0)
                        for kind in ("prefill", "decode")) for what in ("selected", "live"))
    if not live:
        return None
    harness.log(sparse_attention={k: v for k, v in counters.items()
                                  if k.startswith(("dsa_", "swa_"))})
    return 100.0 * chosen / live
