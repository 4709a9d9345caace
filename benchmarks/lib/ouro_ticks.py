"""What the Ouro cell's readers share: the mean shape of a tick of one kind,
the least time the chip could take for it (``lib/opcounts_ouro.py``) against
the p50 of that kind's whole ``tick`` span, and the least time of the decode
walks the traced slice holds. A tick's shape is what it was *fed*
(``lib/olmoe_ticks.py`` ``tick_shape``), not what its fixed-shape program
computes.

**Which input is the program's own report**: the positions a kind of tick's
queries attend, ``kv_full_positions_live_<kind>`` (the fed sequences' lengths,
summed over every layer AND every pass: 192 walks a tick), made on the device
from the write positions and lengths the host handed the tick. It is a
function of the tick's operands alone, not of what the walk read
(``kv_full_positions_read_*`` is that). Without it the runner's mean live
length stands in.
"""

from benchmarks.lib import opcounts_ouro as ops
from benchmarks.lib import program_spans
from benchmarks.lib.dots3_note_ticks import traced_counts  # noqa: F401
from benchmarks.lib.olmoe_ticks import tick_shape as fed_tick_shape


def walks(config):
    """Calls of the attention's walk one tick makes: a layer a pass."""
    return ops.passes(config) * config["num_hidden_layers"]


def tick_shape(kind, program, run, config):
    """``olmoe_ticks.tick_shape`` with ``positions``: the fed slots' live
    positions ONE walk reads in the mean tick, the program's count where it
    made one."""
    shape = fed_tick_shape(kind, program, run, config["serve"])
    if shape is None:
        return None
    shape["positions"] = shape["kv_positions"]
    live = program.get(f"kv_full_positions_live_{kind}")
    if live:
        shape["positions"] = live / (walks(config) * shape["ticks"])
    return shape


def tick_least_ms(config, shape, peaks):
    """(least milliseconds, the bound that applies, FLOPs, bytes) of a tick."""
    flops = ops.tick_flops(config, shape["tokens"], shape["sequences"], shape["positions"])
    nbytes = ops.tick_bytes(config, shape["tokens"], shape["positions"],
                            int8_kv=bool(config["serve"]["kv_quant"]))
    least, bound = ops.roofline_ms(flops, nbytes, peaks)
    return least, bound, flops, nbytes


def tick_roofline_pct(ctx, kind):
    """100 x the least time of the mean ``kind`` tick over the p50 of that
    kind's whole ``tick`` span (``program_spans.tick_ms_p50``: the host's
    share included, so the share cannot pass 100); logs both and the bound
    that applies."""
    from benchmarks.lib import harness

    if ctx["peaks"] is None:
        return None
    config = ctx["cell"].config
    shape = tick_shape(kind, program_spans.ring()[1], ctx["counters"], config)
    tick_ms = program_spans.tick_ms_p50(kind)
    if shape is None or not tick_ms:
        return None
    least, bound, flops, nbytes = tick_least_ms(config, shape, ctx["peaks"])
    harness.log(tick_roofline={"kind": kind, "bound": bound, "least_ms": least,
                               "tick_ms_p50": tick_ms, "flops": flops, "bytes": nbytes,
                               "weight_bytes": ops.decode_weight_bytes(config),
                               "weight_bytes_program": program_weight_bytes(config), "shape": shape})
    return 100.0 * least / tick_ms


def program_weight_bytes(config):
    """What the PROGRAM says a decode tick streams of its weights, from the
    served tree's own leaves (``loop_weight_bytes_streamed`` over the decode
    ticks, which are ``loop_passes_run_decode`` over the passes): logged beside
    ``opcounts_ouro.decode_weight_bytes``, which the roofline uses, as a check
    of the one against the other. None without the counters (the parent)."""
    program = program_spans.ring()[1]
    streamed, passes_run = (program.get("loop_weight_bytes_streamed"),
                            program.get("loop_passes_run_decode"))
    if not streamed or not passes_run:
        return None
    return streamed * ops.passes(config) / passes_run


def decode_walks_least_s(config, counted, peaks):
    """Least seconds the decode walks of the ticks ``counted`` (the decode
    entry of :func:`traced_counts`: sums over those ticks, their layers and
    passes) could take: the fed slots' live rows once a walk. The queries' own
    bytes are left out: a little low, never high."""
    live = counted.get("kv_full_positions_live", 0)
    least, _ = ops.roofline_ms(ops.pool_decode_flops(config, live),
                               ops.pool_decode_bytes(config, live,
                                                     bool(config["serve"]["kv_quant"])), peaks)
    return least / 1e3
