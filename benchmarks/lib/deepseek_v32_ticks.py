"""What the DeepSeek-V3.2 cell's roofline readers share: the least time the
chip could take for the mean tick of one kind (``lib/opcounts_deepseek_v32.py``)
against the p50 of that kind's whole ``tick`` span, and the least time of each
of an indexed layer's kernels over the traced slice. As
``lib/dots3_note_ticks.py``, whose account of which inputs are the program's
own report holds here word for word (the held route's rows and experts
touched; ``dsa_positions_live_*``, ``dsa_positions_selected_*`` and
``latent_positions_live_*``, functions of a tick's operands alone), with
every layer an indexed one; its :func:`traced_counts` is used as it is.
"""

from benchmarks.lib import opcounts_deepseek_v32 as ops
from benchmarks.lib import program_spans
from benchmarks.lib.dots3_note_ticks import traced_counts  # noqa: F401
from benchmarks.lib.nemotron_h_ticks import tick_shape, traced_ticks  # noqa: F401


def _touched_a_layer(config, shape):
    touched = shape.get("touched")
    return None if touched is None else touched / ops.layers(config, "E")


def counted_pairs(config, kind, program, shape):
    """A layer's pairs of the mean ``kind`` tick, the program's counts where
    it made them."""
    pairs = ops.tick_pairs(config, shape["tokens"], shape["sequences"], shape["kv_positions"])
    indexed = ops.layers(config, "F") * shape["ticks"]
    for name, counter in (("live", f"dsa_positions_live_{kind}"),
                          ("selected", f"dsa_positions_selected_{kind}")):
        if program.get(counter) and indexed:
            pairs[name] = program[counter] / indexed
    return pairs


def tick_least_ms(config, shape, peaks, pairs=None):
    """(least milliseconds, the bound that applies, FLOPs, bytes) of a tick."""
    flops = ops.tick_flops(config, shape["tokens"], shape["sequences"], shape["kv_positions"],
                           rows=shape.get("rows"), pairs=pairs)
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
    program = program_spans.ring()[1]
    shape = tick_shape(kind, program, ctx["counters"], config["serve"])
    tick_ms = program_spans.tick_ms_p50(kind)
    if shape is None or not tick_ms:
        return None
    pairs = counted_pairs(config, kind, program, shape)
    ends = program.get(f"latent_positions_live_{kind}")
    if ends:
        # the fed slots' own lengths: a prefilling slot is half its prompt
        # long and a decoding one all of it, which the runner's mean over
        # the busy slots does not tell apart
        shape["kv_positions"] = ends / (ops.layers(config, "F") * shape["ticks"])
    least, bound, flops, nbytes = tick_least_ms(config, shape, ctx["peaks"], pairs)
    harness.log(tick_roofline={"kind": kind, "bound": bound, "least_ms": least,
                               "tick_ms_p50": tick_ms, "flops": flops, "bytes": nbytes,
                               "shape": shape, "pairs_a_layer": pairs,
                               "touched_if_even": ops.layers(config, "E")
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
            ops.routed_flops(config, shape["tokens"], shape.get("rows")),
            ops.moe_kernel_bytes(config, shape["tokens"], touched, shape.get("rows")), peaks)
        total += count * least / 1e3
    return total


def kernel_least_s(config, counted, peaks, kernel):
    """Least seconds one of an indexed layer's kernels could take for the
    ticks ``counted`` (one kind's entry of :func:`traced_counts`: sums over the
    ticks and over the layers): ``kernel`` "index" (the index scores of every
    live pair), "decode" (the absorbed step over the chosen pairs) or "walk" (a
    chunk's expanded walk over them). The queries' own bytes are left out (the
    records do not count them): a little low, never high."""
    live, chosen = counted.get("dsa_positions_live", 0), counted.get("dsa_positions_selected", 0)
    ends = counted.get("latent_positions_live", 0)       # the fed slots' lengths, summed
    if kernel == "index":
        flops, nbytes = ops.index_kernel(config, 0, live, ends)
    elif kernel == "walk":
        flops, nbytes = ops.selected_walk_kernel(config, 0, chosen, ends)
    else:
        flops, nbytes = ops.selected_decode_kernel(config, 0, chosen)
    return ops.roofline_ms(flops, nbytes, peaks)[0] / 1e3


def kernel_roofline_pct(ctx, label, kind, kernel):
    """100 x the least time of an indexed layer's ``kernel`` ("index",
    "decode" or "walk") for the ``kind`` ticks the traced slice holds whole
    over the device time in the slice of the operations the family labels
    ``label``; None where the trace has none or the program wrote no counts."""
    from benchmarks.lib import harness, reducers

    kernel_s = reducers.op_seconds(ctx, label)
    if not kernel_s or ctx["peaks"] is None:
        return None
    counted = traced_counts(ctx["trace"]["window_s"]).get(kind)
    if not counted:
        return None
    least_s = kernel_least_s(ctx["cell"].config, counted, ctx["peaks"], kernel)
    harness.log(kernel_roofline={"kernel": label, "counted": counted, "kernel_s": kernel_s,
                                 "least_s": least_s})
    return 100.0 * least_s / kernel_s if least_s else None
