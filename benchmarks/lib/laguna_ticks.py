"""What the Laguna cell's roofline readers share: the least time the chip
could take for the mean tick of one kind (``lib/opcounts_laguna.py``) against
the p50 of that kind's whole ``tick`` span, and the least time of the grouped
expert matmuls over the traced slice. A tick's shape is what it was *fed*
(``lib/nemotron_h_ticks.py`` ``tick_shape``), not what its fixed-shape program
computes.

**Which inputs are the program's own report**, beside the held route's rows and
experts touched (``lib/nemotron_h_ticks.py``): the cache positions a kind of
tick's queries attend, ``kv_full_positions_live_<kind>`` (the fed sequences'
lengths, summed, every full layer) and ``kv_ring_positions_live_<kind>`` (of
each, what lies inside some real query's window, every sliding layer), made on
the device from the write positions and lengths the host handed the tick.
They are functions of the tick's operands alone, not of what the walk read
(``kv_*_positions_read_*`` is that). Without them the runner's mean live
length stands in.
"""

from benchmarks.lib import opcounts_laguna as ops
from benchmarks.lib import program_spans
from benchmarks.lib.nemotron_h_ticks import tick_shape as held_tick_shape
from benchmarks.lib.olmoe_ticks import traced_ticks  # noqa: F401


def tick_shape(kind, program, run, config):
    """``nemotron_h_ticks.tick_shape`` and the mean tick's ``full_positions``
    and ``window_positions`` a layer, the program's counts where it made them."""
    shape = held_tick_shape(kind, program, run, config["serve"])
    if shape is None:
        return None
    window = min(shape["kv_positions"], shape["sequences"] * config["sliding_window"])
    shape.update(full_positions=shape["kv_positions"], window_positions=window)
    for name, counter, kinds in (("full_positions", f"kv_full_positions_live_{kind}", "F"),
                                 ("window_positions", f"kv_ring_positions_live_{kind}", "W")):
        if program.get(counter) and ops.layers(config, kinds):
            shape[name] = program[counter] / (ops.layers(config, kinds) * shape["ticks"])
    return shape


def _touched_a_layer(config, shape):
    touched = shape.get("touched")
    return None if touched is None else touched / ops.layers(config, "E")


def tick_least_ms(config, shape, peaks):
    """(least milliseconds, the bound that applies, FLOPs, bytes) of a tick."""
    where = (shape["tokens"], shape["sequences"], shape["full_positions"],
             shape["window_positions"])
    flops = ops.tick_flops(config, *where, rows=shape.get("rows"))
    nbytes = ops.tick_bytes(config, *where, int8_kv=bool(config["serve"]["kv_quant"]),
                            touched=_touched_a_layer(config, shape))
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
                               "shape": shape, "touched_if_even": ops.layers(config, "E")
                               * ops.experts_touched(config, shape["tokens"])})
    return 100.0 * least / tick_ms


def moe_kernels_least_s(config, program, run, peaks, ticks):
    """Least seconds the grouped expert matmuls could take over ``ticks``
    (``{kind: count}``), each at its kind's mean shape."""
    total = 0.0
    for kind, count in ticks.items():
        shape = tick_shape(kind, program, run, config)
        if shape is None:
            continue
        least, _ = ops.roofline_ms(
            ops.expert_flops(config, shape["tokens"], shape.get("rows")),
            ops.moe_kernel_bytes(config, shape["tokens"], _touched_a_layer(config, shape),
                                 shape.get("rows")), peaks)
        total += count * least / 1e3
    return total
