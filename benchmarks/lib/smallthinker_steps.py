"""What the SmallThinker cell's readers share: the program's own counts of
its training steps (``deepspeed_tpu/utils/trace.py`` counters the engine
fills after every step from what the held expert layers counted on the
device, ``runtime/engine.py::_record_step_counts``; the flash kernels' tile
walks counted where they are traced), turned into a step's shape. A program
without those counters (the parent) gives None everywhere."""

from benchmarks.lib import program_spans


def counts(ctx):
    """``{"steps", "rows_routed", "rows_visited", "copies", "load_max"}``
    summed over every step the process ran, or None. ``steps`` comes from
    the copies: every step routes ``tokens x top_k`` copies a layer."""
    _, counters = program_spans.ring()
    if not counters.get("moe_copies"):
        return None
    config = ctx["cell"].config
    per_step = (ctx["counters"]["tokens_per_step"] * config["moe_num_active_primary_experts"]
                * config["num_hidden_layers"])
    out = {name: counters.get("moe_" + name, 0)
           for name in ("rows_routed", "rows_visited", "copies", "load_max", "rows_buffered")}
    out["steps"] = out["copies"] / per_step
    return out


def by_layer():
    """``{count: {layer: mean a step}}`` from the ring's ``count:moe_<name>``
    records (the count in ``uid``, ``layer_<i>`` in ``kind``), for the log."""
    records, _ = program_spans.ring()
    sums = {}
    for r in records:
        if r.name.startswith("count:moe_") and r.kind:
            per = sums.setdefault(r.name[len("count:moe_"):], {}).setdefault(r.kind, [0, 0])
            per[0] += r.uid
            per[1] += 1
    return {name: {layer: total / n for layer, (total, n) in sorted(layers.items())}
            for name, layers in sums.items()}


def rows_per_token(ctx):
    """Expert rows a token owed this chip a layer, as the program counted
    them; None without the counters."""
    got = counts(ctx)
    if got is None:
        return None
    config = ctx["cell"].config
    return got["rows_routed"] / got["copies"] * config["moe_num_active_primary_experts"]


def window_tiles():
    """``(live tiles a walk of a window layer, of a full layer, walks of each)``
    from the flash kernels' trace-time tile counts, or None."""
    _, c = program_spans.ring()
    walks_w, walks_f = c.get("attn_walks_window"), c.get("attn_walks_full")
    if not walks_w or not walks_f:
        return None
    live = lambda kind: c.get(f"attn_tiles_{kind}_interior", 0) + c.get(f"attn_tiles_{kind}_edge", 0)  # noqa: E731
    return live("window") / walks_w, live("full") / walks_f, walks_w, walks_f
