"""What the dots3-note cell's roofline readers share: the least time the chip
could take for the mean tick of one kind (``lib/opcounts_dots3_note.py``)
against the p50 of that kind's whole ``tick`` span, and the least time of
each new kernel over the traced slice. A tick's shape is what it was *fed*
(``lib/nemotron_h_ticks.py`` ``tick_shape``), not what its fixed-shape
program computes.

**Which inputs are the program's own report**, beside the held route's rows
and experts touched (``lib/nemotron_h_ticks.py``): the query-position pairs
of a kind of tick, where the program counted them (``dsa_positions_live_*``,
``dsa_positions_selected_*``: sums over the real queries of the positions at
or before each and of ``min(that, index_topk)``, made on the device from the
write positions and lengths the host handed the tick, all full layers), and
the fed slots' lengths summed (``latent_positions_live_*``). They are
functions of the tick's operands alone, not of what the kernels did. Without
them the mean sequence stands in (``opcounts.tick_pairs``). The kernels'
shares take the same counts tick by tick from the ring
(:func:`traced_counts`), for the ticks the traced slice holds.
"""

from benchmarks.lib import opcounts_dots3_note as ops
from benchmarks.lib import program_spans
from benchmarks.lib.nemotron_h_ticks import tick_shape, traced_ticks  # noqa: F401


def _touched_a_layer(config, shape):
    touched = shape.get("touched")
    return None if touched is None else touched / ops.layers(config, "E")


def counted_pairs(config, kind, program, shape):
    """A layer's pairs of the mean ``kind`` tick, the program's counts where
    it made them."""
    pairs = ops.tick_pairs(config, shape["tokens"], shape["sequences"], shape["kv_positions"])
    full = ops.layers(config, "F") * shape["ticks"]
    for name, counter in (("live", f"dsa_positions_live_{kind}"),
                          ("selected", f"dsa_positions_selected_{kind}")):
        if program.get(counter) and full:
            pairs[name] = program[counter] / full
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
            ops.expert_flops(config, shape["tokens"], shape.get("rows")),
            ops.moe_kernel_bytes(config, shape["tokens"], touched, shape.get("rows")), peaks)
        total += count * least / 1e3
    return total


def traced_counts(window_s):
    """What the ticks the profiler's slice holds WHOLE counted, by kind:
    ``{kind: {"ticks": n, "dsa_positions_live": .., "dsa_positions_selected":
    .., "dsa_index_keys_read": .., "latent_positions_live": .., ...}}``, the
    sums of the program's ``count:`` ring records (one a count a tick, the
    count in ``uid``; ``scheduler._read_back``) over the newest scheduler's
    ticks that started inside the slice (``olmoe_ticks.traced_ticks``' ticks).
    The slice is a few seconds at the window's end and its ticks are not the
    process's mean tick (fewer slots prefill at once than in the pre-roll: a
    third fewer live positions a prefill tick), so a kernel's device time IN
    the slice is held against what THESE ticks were owed. A tick cut by the
    slice's start is left out: the shares read low by at most that tick,
    never high. ``{}`` on a program that writes no such records."""
    records, _ = program_spans.ring()
    units = program_spans._units(records, "tick")
    if not units:
        return {}
    source = units[-1][0].source
    opened = units[-1][0].end - window_s - 1e-3
    out, pending = {}, {}
    for r in records:
        if r.source != source:
            continue
        if r.name.startswith("count:"):
            pending[r.name[len("count:"):]] = pending.get(r.name[len("count:"):], 0) + int(r.uid)
        elif r.name == "tick":
            if pending and r.start >= opened and r.kind not in (None, "idle"):
                mine = out.setdefault(r.kind, {"ticks": 0})
                mine["ticks"] += 1
                for name, n in pending.items():
                    mine[name] = mine.get(name, 0) + n
            pending = {}
    return out


def kernel_least_s(config, counted, peaks, kernel):
    """Least seconds one of a full layer's kernels could take for the ticks
    ``counted`` (one kind's entry of :func:`traced_counts`: sums over the
    ticks and over the full layers): ``kernel`` "index" (the index scores of
    every live pair), "decode" (the absorbed step over the chosen pairs) or
    "walk" (a chunk's expanded walk over them). The queries' own bytes are
    left out (the records do not count them): a little low, never high."""
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
    """100 x the least time of a full layer's ``kernel`` ("index", "decode"
    or "walk") for the ``kind`` ticks the traced slice holds whole over the
    device time in the slice of the operations the family labels ``label``;
    None where the trace has none or the program wrote no counts."""
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
