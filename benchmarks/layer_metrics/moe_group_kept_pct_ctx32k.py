"""What group-limited routing exists to bound: 100 x the real rows whose kept
groups include the group of an expert held here over the real rows routed, all
expert layers, both kinds of tick (``moe_rows_group_kept_*`` /
``moe_rows_group_routed_*``: made on the device from the group mask the route
already has, read back behind the tick's tokens). The eight held experts lie in
group 0 of eight, of which four are kept: 50 under an even router, where a flat
top-8 of 256 would let 1 - (7/8)^8 = 66% of the rows reach the group. A program
whose router has no groups has no such counter and reads nothing."""

from benchmarks.lib import harness, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    kept, routed = (sum(counters.get(f"moe_rows_group_{what}_{kind}", 0)
                        for kind in ("prefill", "decode")) for what in ("kept", "routed"))
    if not routed:
        return None
    harness.log(moe_groups={k: v for k, v in counters.items() if k.startswith("moe_rows_group_")})
    return 100.0 * kept / routed
