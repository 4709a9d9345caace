"""What the Nemotron-H cell's roofline readers share: the least time the
chip could take for the mean tick of one kind (``lib/opcounts_nemotron_h.py``)
against the p50 of that kind's whole ``tick`` span. A tick's shape is what
it was *fed* (``lib/olmoe_ticks.py`` ``tick_shape``: the program's and the
runner's counters), not what its fixed-shape program computes.

**Which inputs are the program's own report.** The sizes, the operation
counts and the peaks are the benchmark's. The tokens fed, the ticks run and
the ``tick`` span are the program's recorder, as in every serving
cell. Two more inputs here are counted by the code under test, on the
device: ``moe_rows_routed_<kind>`` and ``moe_experts_touched_<kind>``
(``MOELayer`` with ``experts_held``; which of a token's experts are held
here is decided there, and no event of the device trace carries a group's
size). A program that over-counted them would read a higher share of its
roofline. What the benchmark can hold against them it logs beside them:
``touched_if_even``, the held experts the fed tokens reach under an even
router (``opcounts_nemotron_h.experts_touched``), an upper mark a seeded
router stays under. ``moe_rows_computed`` (``moe_pad_pct_reason``) is a
model of the kernel's row tiles written beside the kernel
(``grouped_matmul.rows_visited``), not a count the kernel makes.
"""

from benchmarks.lib import opcounts_nemotron_h as ops
from benchmarks.lib import program_spans
from benchmarks.lib import olmoe_ticks
from benchmarks.lib.olmoe_ticks import traced_ticks  # noqa: F401


def tick_shape(kind, program, run, serve):
    """``olmoe_ticks.tick_shape`` and, where the program counted them by
    the kind of tick, the mean tick's ``rows`` (expert rows routed to
    experts held here, all layers) and ``touched`` (held experts a layer
    that got a row): a seeded router is not even, and a roofline that
    assumed it were would count weights the tick never streamed."""
    shape = olmoe_ticks.tick_shape(kind, program, run, serve)
    if shape is not None and program.get(f"moe_experts_touched_{kind}") is not None:
        shape["rows"] = program[f"moe_rows_routed_{kind}"] / shape["ticks"]
        shape["touched"] = program[f"moe_experts_touched_{kind}"] / shape["ticks"]
    return shape


def _touched_a_layer(config, shape):
    touched = shape.get("touched")
    return None if touched is None else touched / ops.layers(config, "E")


def tick_least_ms(config, shape, peaks):
    """(least milliseconds, the bound that applies, FLOPs, bytes) of a tick."""
    flops = ops.tick_flops(config, shape["tokens"], shape["sequences"], shape["kv_positions"],
                           rows=shape.get("rows"))
    nbytes = ops.tick_bytes(config, shape["tokens"], shape["sequences"], shape["kv_positions"],
                            int8_kv=bool(config["serve"]["kv_quant"]),
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
    shape = tick_shape(kind, program_spans.ring()[1], ctx["counters"], config["serve"])
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
        shape = tick_shape(kind, program, run, config["serve"])
        if shape is None:
            continue
        touched = _touched_a_layer(config, shape)
        least, _ = ops.roofline_ms(
            ops.expert_flops(config, shape["tokens"], shape.get("rows")),
            ops.moe_kernel_bytes(config, shape["tokens"], touched, shape.get("rows")), peaks)
        total += count * least / 1e3
    return total


def ticks_run(program, serve):
    """Prefill and decode ticks the process ran, from the program's counters."""
    slots = serve["slots"]
    return (program.get("prefill_positions_computed", 0) / (slots * serve["prefill_chunk"])
            + program.get("decode_slots_computed", 0) / slots)
