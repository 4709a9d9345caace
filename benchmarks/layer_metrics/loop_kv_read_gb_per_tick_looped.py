"""What a decode tick reads of the pools over its passes, in GB:
``kv_full_positions_read_decode`` (the positions the decode walks were bounded
to: each fed slot's live length rounded up to the kernel's block, every layer,
every PASS; counted on the device) x the bytes a position holds a layer a pass
(``lib/opcounts_ouro.py``: 4,160 as int8 codes with their scales) over the
decode ticks the process ran. A looped stack reads a cache a pass: four times
what a stack of the same depth reads. Nothing on a program without the
counter."""

from benchmarks.lib import opcounts_ouro, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    config = ctx["cell"].config
    ticks = counters.get("decode_slots_computed", 0) / config["serve"]["slots"]
    positions = counters.get("kv_full_positions_read_decode")
    if not positions or not ticks:
        return None
    each = opcounts_ouro.kv_bytes_per_position(config, bool(config["serve"]["kv_quant"]))
    return positions * each / ticks / 1e9
