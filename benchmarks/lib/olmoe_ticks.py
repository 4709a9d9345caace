"""What the OLMoE cell's roofline readers share: the mean shape of a tick
of one kind, from the program's and the runner's counters, the least time
the chip could take for it (``lib/opcounts_olmoe.py``), and how many ticks
of each kind the traced slice holds.

A tick's shape is what it was *fed*, not what its fixed-shape program
computes: a decode tick of 32 slots with 20 of them decoding owes 20
tokens' work. The counters are totals (the program's over the process, the
runner's over the untraced window), so the shape is a mean over ticks.
"""

from benchmarks.lib import opcounts_olmoe as ops
from benchmarks.lib import program_spans


def tick_shape(kind, program, run, serve):
    """``{"ticks", "tokens", "sequences", "kv_positions"}`` of the mean
    ``kind`` tick ("decode" or "prefill"), or None without the counters.
    ``program``: the recorder's counters; ``run``: the runner's; ``serve``:
    the configuration's ``serve`` block. ``kv_positions`` is the live cache
    positions a tick held (the runner's counter, all busy slots) times the
    share of the busy slots this kind of tick fed."""
    slots, chunk = serve["slots"], serve["prefill_chunk"]
    if kind == "decode":
        fed, computed, width = "decode_slots_fed", "decode_slots_computed", 1
    else:
        fed, computed, width = "prefill_positions_fed", "prefill_positions_computed", chunk
    n_ticks = program.get(computed, 0) / (slots * width)
    working = run.get("slot_ticks", 0) / slots
    if not n_ticks or not working:
        return None
    tokens = program[fed] / n_ticks
    sequences = tokens / width
    busy = run["slot_ticks_busy"] / working
    live = run["kv_positions_live"] / working
    return {"ticks": n_ticks, "tokens": tokens, "sequences": sequences,
            "kv_positions": live * min(1.0, sequences / busy) if busy else 0.0}


def tick_least_ms(config, shape, peaks):
    """(least milliseconds, the bound that applies, FLOPs, bytes) of a tick."""
    flops = ops.tick_flops(config, shape["tokens"], shape["kv_positions"], shape["sequences"])
    nbytes = ops.tick_bytes(config, shape["tokens"], shape["kv_positions"],
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
    shape = tick_shape(kind, program_spans.ring()[1], ctx["counters"], config["serve"])
    tick_ms = program_spans.tick_ms_p50(kind)
    if shape is None or not tick_ms:
        return None
    least, bound, flops, nbytes = tick_least_ms(config, shape, ctx["peaks"])
    harness.log(tick_roofline={"kind": kind, "bound": bound, "least_ms": least,
                               "tick_ms_p50": tick_ms, "flops": flops, "bytes": nbytes,
                               "shape": shape})
    return 100.0 * least / tick_ms


def traced_ticks(window_s):
    """Ticks of each kind the profiler's slice holds, ``{kind: count}``: the
    slice is the last ``window_s`` seconds of the run's ticks (the runner
    opens it between two ticks and closes it after the last), so these are
    the newest scheduler's non-idle ticks that started no earlier than
    ``window_s`` before the last one ended."""
    records, _ = program_spans.ring()
    units = program_spans._units(records, "tick")
    if not units:
        return {}
    opened = units[-1][0].end - window_s - 1e-3
    counts = {}
    for tick, _ in units:
        if tick.start >= opened and tick.kind != "idle":
            counts[tick.kind] = counts.get(tick.kind, 0) + 1
    return counts


def moe_kernels_least_s(config, program, run, peaks, ticks):
    """Least seconds the expert layer's kernels could take over ``ticks``
    (``{kind: count}``), each at its kind's mean shape: the routed FLOPs
    against the touched experts' weights and the rows moved."""
    total = 0.0
    for kind, count in ticks.items():
        shape = tick_shape(kind, program, run, config["serve"])
        if shape is None:
            continue
        least, _ = ops.roofline_ms(ops.expert_flops(config, shape["tokens"]),
                                   ops.moe_kernel_bytes(config, shape["tokens"]), peaks)
        total += count * least / 1e3
    return total
