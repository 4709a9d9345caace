"""Recurrent state a tick reads and writes, in GB: the program's
``ssm_state_bytes_touched`` (every tick: all the slots the program
computes, live or parked, x the state and tail a slot holds, read + write)
over the prefill and decode ticks the process ran. What the tick's
roofline counts is the fed slots' share of it.

This is the program's statement of what its fixed-shape tick touches, made
on the host (``scheduler._count_state``), not a measurement: 2 x slots x
bytes a slot, 2.72 GB at 64 slots, every tick. It moves when the program's
structure does (a tick that leaves parked slots' state where it lies would
count less), and with nothing else; the device trace names no state-step
fusion to time against it (``PERF.md`` section 7)."""

from benchmarks.lib import nemotron_h_ticks, program_spans


def read(ctx):
    _, counters = program_spans.ring()
    ticks = nemotron_h_ticks.ticks_run(counters, ctx["cell"].config["serve"])
    if not counters.get("ssm_state_bytes_touched") or not ticks:
        return None
    return counters["ssm_state_bytes_touched"] / ticks / 1e9
