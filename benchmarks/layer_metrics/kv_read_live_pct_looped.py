"""Share of the pool positions the attention's walks were bounded to that hold
a token of the sequence they were read for, over all the walks of a looped
stack (a layer a PASS: 192 a tick): 100 x ``kv_full_positions_live_*`` /
``kv_full_positions_read_*``, both kinds of tick, totals of the process
(counted on the device from the walk's own trip counts:
``models/common.py`` ``kv_reads``, summed over the passes). Each pass's pair is
logged beside it (``kv_full_positions_*_pass<t>_<kind>``, from
``kv_pass_reads``): the passes walk the same lengths, so a pass that read more
than another would be a fault. A decode tick's kernel reads each slot as far as
that slot goes, in whole blocks; a chunk walks one slot's pool in blocks up to
that slot's length."""

from benchmarks.lib import harness, opcounts_ouro, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    kinds = ("prefill", "decode")
    total = {what: sum(counters.get(f"kv_full_positions_{what}_{kind}", 0) for kind in kinds)
             for what in ("read", "live")}
    if not total["read"]:
        return None
    by_pass = [{what: sum(counters.get(f"kv_full_positions_{what}_pass{t}_{kind}", 0)
                          for kind in kinds) for what in ("read", "live")}
               for t in range(opcounts_ouro.passes(ctx["cell"].config))]
    harness.log(kv_full_positions_looped={"all_walks": total, "by_pass": by_pass})
    return 100.0 * total["live"] / total["read"]
