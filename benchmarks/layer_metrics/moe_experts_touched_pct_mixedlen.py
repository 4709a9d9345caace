"""Share of a sparse layer's experts a decode tick touches: 100 x
``moe_experts_touched_decode`` / (``num_experts`` x sparse layers x decode
ticks), counted on the device (an expert with a row of a real token; the held
route's fourth count) and summed over the layers and the process's decode
ticks. Every touched expert's three matrices are streamed for as little as one
row: 32 slots x 8 of 256 reach ~63% under an even router, so this is how much
of a layer's 0.8 GB of experts a decode tick pays for."""

from benchmarks.lib import opcounts_laguna, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    config = ctx["cell"].config
    ticks = counters.get("decode_slots_computed", 0) / config["serve"]["slots"]
    touched = counters.get("moe_experts_touched_decode")
    if not ticks or touched is None:
        return None
    return 100.0 * touched / (config["num_experts"] * opcounts_laguna.layers(config, "E") * ticks)
