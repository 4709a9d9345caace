"""The decode walk's kernel at its roofline: the least time the chip could take
for every decode tick's walks in the traced slice (``lib/ouro_ticks.py``
``decode_walks_least_s``: the fed slots' live positions' rows once a walk,
4,160 B each as int8 codes with their scales; ``lib/opcounts_ouro.py``
``pool_decode_bytes``) over those kernels' device time (``pallas:attn:decode``:
``ops/pallas/pool_decode.py``'s call, which the family's ``op_label`` names from
``%pool_decode*``). The time is the device trace's; the live positions are the
program's own count of THOSE ticks' operands (the ``count:`` records of the
ticks the slice holds whole). A program with no such kernel reads nothing."""

from benchmarks.lib import harness, ouro_ticks, reducers


def read(ctx):
    kernel_s = reducers.op_seconds(ctx, "^pallas:attn:decode")
    if not kernel_s or ctx["peaks"] is None:
        return None
    counted = ouro_ticks.traced_counts(ctx["trace"]["window_s"]).get("decode")
    if not counted:
        return None
    least_s = ouro_ticks.decode_walks_least_s(ctx["cell"].config, counted, ctx["peaks"])
    harness.log(pool_decode_roofline={"traced_decode_ticks": counted["ticks"],
                                      "kernel_s": kernel_s, "least_s": least_s,
                                      "live_positions": counted.get("kv_full_positions_live")})
    return 100.0 * least_s / kernel_s if least_s else None
