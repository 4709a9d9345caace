"""Share of the full layers' pool positions the attention's walk was bounded
to that hold a token of the sequence they were read for: 100 x
``kv_full_positions_live_*`` / ``kv_full_positions_read_*``, both kinds of
tick, both full layers, totals of the process (counted on the device from the
walk's own trip counts: ``models/llama.py`` ``kv_reads``). A chunk walks one
slot's pool in blocks up to that slot's length; a decode tick walks every
slot's pool together as far as the LONGEST slot goes, so under heavy-tailed
lengths most of what it reads lies past the shorter slots' ends."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    by_kind = {kind: [counters.get(f"kv_full_positions_{what}_{kind}", 0)
                      for what in ("read", "live")] for kind in ("prefill", "decode")}
    read_, live = (sum(pair[i] for pair in by_kind.values()) for i in (0, 1))
    if not read_:
        return None
    harness.log(kv_full_positions={kind: {"read": r, "live": v} for kind, (r, v) in by_kind.items()})
    return 100.0 * live / read_
