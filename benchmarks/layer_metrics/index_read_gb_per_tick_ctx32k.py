"""Index keys a decode tick reads in the 32k-context cell, in GB:
``dsa_index_keys_read_decode`` (the positions of the index-key pools the index
kernels were bounded to: each fed slot's live length rounded up to the kernel's
1,024-position block, all six layers; counted on the device) x 256 B a key
(``index_head_dim`` bfloat16) over the decode ticks the process ran. Beside it,
on an earlier line, what the attention is owed of the latent pools in the same
ticks (``dsa_positions_selected_decode`` x 1,152 B: at most 2,048 a slot a
layer): sixteen slots at ~25,000 live are 0.6 GB of keys a tick to 0.23 GB of
chosen latents. Nothing on a program without the counter."""

from benchmarks.lib import harness, opcounts_deepseek_v32, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    config = ctx["cell"].config
    ticks = counters.get("decode_slots_computed", 0) / config["serve"]["slots"]
    keys = counters.get("dsa_index_keys_read_decode")
    if not keys or not ticks:
        return None
    wide = opcounts_deepseek_v32.CACHE_BYTES
    owed = counters.get("dsa_positions_selected_decode", 0) * opcounts_deepseek_v32.latent_width(
        config) * wide / ticks / 1e9
    harness.log(decode_tick_reads_gb={"index_keys": keys * config["index_head_dim"] * wide
                                      / ticks / 1e9, "chosen_latents_owed": owed})
    return keys * config["index_head_dim"] * wide / ticks / 1e9
