"""Share of the latent-pool positions the attention reads that hold a token:
100 x (``latent_positions_live_prefill`` + ``.._decode``) /
(``latent_positions_read_prefill`` + ``.._decode``), the program's counters
by kind of tick, all layers, totals of the process. Both forms read a fed
slot's pool in whole blocks up to its live length (the prefill walk in key
blocks of 512, the decode kernel in blocks of 1,024) and no other slot's, so
on the chip this reads in the nineties; a program whose decode step reads
every slot's whole pool (XLA's two matmuls, the first form this cell ran)
reads ~40. 100 less this is what is read past the live lengths. The split
by kind goes to an earlier output line.

The counts are made on the device, in the traced program, from the values
that bound its loops (the walk's trip counts, the lengths the decode kernel
skips by: ``models/deepseek_v3.py`` ``latent_reads``, a cache leaf a layer),
summed over the layers and read back behind the tick's tokens as the
``moe_rows_*`` are: a walk bounded elsewhere moves them; a kernel that read
past the lengths it is handed would not."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    read_, live = (sum(counters.get(f"latent_positions_{what}_{kind}", 0)
                       for kind in ("prefill", "decode")) for what in ("read", "live"))
    if not read_:
        return None
    harness.log(latent_positions={k: v for k, v in counters.items() if k.startswith("latent_")})
    return 100.0 * live / read_
