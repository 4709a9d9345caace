"""Share of the real tokens' expert copies whose expert another chip of
the deployment holds: 100 x ``moe_rows_elsewhere`` / (``moe_rows_routed`` +
``moe_rows_elsewhere``), counted on the device. With a quarter of
the experts held (128 of 512, 64 of 256) and an even router it reads 75, with
32 of 256 held 87.5; this chip computes none of them. One entry for the
serving cells that hold a share of their experts."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    here, away = counters.get("moe_rows_routed"), counters.get("moe_rows_elsewhere")
    if here is None or away is None or not here + away:
        return None
    harness.log(moe_rows={"here": here, "elsewhere": away,
                          "computed": counters.get("moe_rows_computed")})
    return 100.0 * away / (here + away)
