"""Share of the real tokens' expert copies whose expert another chip of the
deployment holds: as ``moe_elsewhere_pct_reason``. With 32 of 256 experts
held and an even router it reads 87.5; this chip computes none of them."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    here, away = counters.get("moe_rows_routed"), counters.get("moe_rows_elsewhere")
    if here is None or away is None or not here + away:
        return None
    harness.log(moe_rows={"here": here, "elsewhere": away,
                          "computed": counters.get("moe_rows_computed")})
    return 100.0 * away / (here + away)
