"""How long the scheduler kept the chip waiting in the chat cell, as the
program itself saw it: 100 x the seconds of the ``device_dry`` records (the
lower bound) inside the steady non-idle ticks over those ticks' seconds. A
program dispatched behind another looks at that other's tokens
(``is_ready()``) entering ``build_inputs``, entering ``dispatch`` and when
``launch`` returns; ended at the last look, the device had nothing queued
from the first look that saw it ended until the launch returned. The upper
bound (from the look before) and the ticks by phase are on the
``program_dispatch_split`` line. A tick dispatched into an empty scheduler
(after an idle stretch) is not the host's doing and is left out. 0 where the
program looked and the device was always fed; None where it does not look."""

from benchmarks.lib import program_dispatch


def read(ctx):
    return program_dispatch.device_dry_pct()
