"""What the JoyAI-LLM-Flash cell's roofline readers share: the least time
the chip could take for the mean tick of one kind
(``lib/opcounts_joyai_llm_flash.py``) against the p50 of that kind's
whole ``tick`` span. A tick's shape is what it was *fed*
(``lib/nemotron_h_ticks.py`` ``tick_shape``: the program's and the runner's
counters, the held route's device-side rows and experts touched among them;
that file says which inputs are the program's own report), not what its
fixed-shape program computes, nor what its attention reads of dead positions.
"""

from benchmarks.lib import opcounts_joyai_llm_flash as ops
from benchmarks.lib import program_spans
from benchmarks.lib.nemotron_h_ticks import tick_shape, traced_ticks  # noqa: F401


def _touched_a_layer(config, shape):
    touched = shape.get("touched")
    return None if touched is None else touched / ops.layers(config, "E")


def tick_least_ms(config, shape, peaks):
    """(least milliseconds, the bound that applies, FLOPs, bytes) of a tick."""
    flops = ops.tick_flops(config, shape["tokens"], shape["sequences"], shape["kv_positions"],
                           rows=shape.get("rows"))
    nbytes = ops.tick_bytes(config, shape["tokens"], shape["sequences"], shape["kv_positions"],
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


def decode_kernels_least_s(config, program, run, peaks, decode_ticks):
    """Least seconds the absorbed-step kernels of ``decode_ticks`` decode
    ticks could take, each at the mean decode tick's shape: every layer's
    kernel reads the fed slots' live positions once."""
    shape = tick_shape("decode", program, run, config["serve"])
    if shape is None:
        return 0.0
    least, _ = ops.roofline_ms(
        ops.decode_kernel_flops(config, shape["kv_positions"]),
        ops.decode_kernel_bytes(config, shape["tokens"], shape["kv_positions"]), peaks)
    return decode_ticks * ops.layers(config, "A") * least / 1e3
