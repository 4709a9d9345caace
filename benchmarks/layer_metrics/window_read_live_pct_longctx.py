"""Share of the ring positions the window layers read that lie in a window of
the request they were read for: 100 x ``swa_ring_positions_live_*`` /
``swa_ring_positions_read_*``, both kinds of tick, every sliding layer
(counted on the device: a decode tick reads the whole ring of every fed slot,
768 positions of which 513 are the query's window, fewer while the request
is shorter; a prefill walk reads the ring's blocks up to the slot's length,
of which the chunk and the 512 before it count). What is read and masked is
the rest: the ring's other half, and after a join the last tenant's rows.
Nothing on a program without the counters."""

from benchmarks.lib import program_spans


def read(ctx):
    _, counters = program_spans.ring()
    read_, live = (sum(counters.get(f"swa_ring_positions_{what}_{kind}", 0)
                       for kind in ("prefill", "decode")) for what in ("read", "live"))
    return 100.0 * live / read_ if read_ else None
