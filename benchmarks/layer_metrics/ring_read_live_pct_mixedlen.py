"""Share of the ring positions the sliding layers' attention read that lie
inside a window of the sequence they were read for: 100 x
``kv_ring_positions_live_*`` / ``kv_ring_positions_read_*``, both kinds of
tick, the three sliding layers, totals of the process. A decode tick reads
every slot's whole ring (1,024 positions, of which a query's window is 512,
fewer while the request is shorter, none of a parked slot); a chunk reads its
slot's ring up to the slot's length, of which the chunk and the 511 before it
count."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    totals = {what: {kind: counters.get(f"kv_ring_positions_{what}_{kind}", 0)
                     for kind in ("prefill", "decode")} for what in ("read", "live")}
    if not sum(totals["read"].values()):
        return None
    harness.log(kv_ring_positions=totals, kv_ring_bytes_written=counters.get("kv_ring_bytes_written"))
    return 100.0 * sum(totals["live"].values()) / sum(totals["read"].values())
