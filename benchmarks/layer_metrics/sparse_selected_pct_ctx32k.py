"""How sparse the attention is in the 32k-context cell: 100 x positions attended
over positions live, summed over the real queries of all six layers, both kinds
of tick (``dsa_positions_selected_*`` / ``dsa_positions_live_*``: a query at
position ``t`` attends ``min(t + 1, index_topk)`` of ``t + 1``; counted on the
device from the tick's write positions and lengths and read back behind its
tokens). Lower is sparser: a prompt of ~24,600 summed from position 0 reads ~16,
a decode query at ~25,000 reads 8, and the two kinds' own shares go to an
earlier line. A program with no indexer has no such counter and reads nothing."""

from benchmarks.lib import harness, program_spans

KINDS = ("prefill", "decode")


def read(ctx):
    _, counters = program_spans.ring()
    chosen, live = ({kind: counters.get(f"dsa_positions_{what}_{kind}", 0) for kind in KINDS}
                    for what in ("selected", "live"))
    if not sum(live.values()):
        return None
    harness.log(sparse_selected_pct_by_kind={
        kind: 100.0 * chosen[kind] / live[kind] for kind in KINDS if live[kind]})
    return 100.0 * sum(chosen.values()) / sum(live.values())
