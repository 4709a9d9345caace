"""Index keys a decode tick reads, in GB: ``dsa_index_keys_read_decode`` (the
positions of the index-key pools the index kernels were bounded to: each fed
slot's live length rounded up to the kernel's 1,024-position block, every
full layer; counted on the device from the lengths the kernel skips by) x
256 B a key (``index_head_dim`` bfloat16) over the decode ticks the process
ran. It grows with the live context where the latents the attention is owed
(2,048 a slot) do not: past ~9,000 live positions a slot the keys are the
larger read. Nothing on a program without the counter."""

from benchmarks.lib import program_spans


def read(ctx):
    _, counters = program_spans.ring()
    config = ctx["cell"].config
    ticks = counters.get("decode_slots_computed", 0) / config["serve"]["slots"]
    keys = counters.get("dsa_index_keys_read_decode")
    if not keys or not ticks:
        return None
    return keys * config["index_head_dim"] * 2 / ticks / 1e9
