"""Controls for the JoyAI-LLM-Flash serving cell's reference check: does the
comparison that decides ``correct`` refuse a server computed below the
precision the configuration states?

As ``tools/nemotron_h_controls.py`` (whose ``fp8_family`` this uses): each
control stands **in the program's place**, a server built exactly as the
cell builds it (``benchmarks/runners/serve.py::_server``) with one thing
lowered, serving the cell's two checked requests through chunked prefill and
absorbed decode, then held to the plain reference over the configuration's
own weights by the runner's own ``_compare_with_reference``: the ``ok``
printed is the ``correct`` the cell would have reported for that server.

* ``program``: the server as it is.
* ``fp8_weights``: the server's matrices rounded to float8 e4m3 (and back to
  the served type): the nearest precision below the stated bfloat16.

Each line also carries what the checked requests' decode ticks touched of
the held experts a layer (``experts_touched_a_decode_tick_a_layer``, the
program's device-side count): with two slots decoding an even router reaches
64 x (1 - (255/256)^16) = 3.9.

    python3 tools/joyai_llm_flash_controls.py --seed <n> [<n> ...] [--control <name> ...]

Prints one JSON line a seed and control. Runs on whatever device JAX finds;
the numbers that count are the chip's.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CONTROLS = ("program", "fp8_weights")
WORKLOAD = "serve-joyai-llm-flash-longdoc-sat"


def run_control(cell, seed, control):
    """One server, one comparison: the line's fields."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import harness, opcounts_joyai_llm_flash as ops
    from deepspeed_tpu.utils import trace
    from nemotron_h_controls import fp8_family

    family, runner = cell.family, cell.runner
    gc.collect()    # an earlier control's server: 6.4 GB of weights and 4.5 of pool do not fit twice
    t0 = time.time()
    env = harness.Env(seed, 0, 0, harness.Setup(t0), jax.devices()[:1], harness.Tracer(False, ""))
    before = dict(trace.recorder().counters)
    engine, sched = runner._server(cell, env, fp8_family(family) if control == "fp8_weights"
                                   else family)
    sched.warmup()
    reqs = runner._checked_requests(cell, env, sched)
    counted = {k: v - before.get(k, 0) for k, v in trace.recorder().counters.items()}
    line = {"seed": seed, "control": control}
    ticks = counted.get("decode_slots_computed", 0) / sched.slots
    if ticks:
        line["experts_touched_a_decode_tick_a_layer"] = (
            counted.get("moe_experts_touched_decode", 0) / ticks / ops.layers(cell.config, "E"))
    if control == "fp8_weights":
        # show that the rounding was made (rounding again changes nothing), then
        # let the reference read the configuration's own weights, not this server's
        head = family.to_reference(engine.params)["head"]
        line["weights_are_fp8_values"] = bool(
            (head.astype(jnp.float8_e4m3fn).astype(head.dtype) == head).all())
        del engine, sched, head
        gc.collect()
        engine, sched = runner._server(cell, env, family)
    del sched
    gc.collect()
    line.update(runner._compare_with_reference(cell, family, engine, reqs))
    line.update(device=jax.devices()[0].device_kind, seconds=round(time.time() - t0, 1))
    return line


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default=WORKLOAD)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--control", nargs="+", default=list(CONTROLS), choices=CONTROLS)
    parser.add_argument("--root", default=ROOT)
    args = parser.parse_args(argv)

    from benchmarks.lib import harness
    from envutil import use_compile_cache

    use_compile_cache()
    cell = harness.Cell(args.root, harness.load_json(args.root, "BENCHMARK.json"), args.workload)
    for seed in args.seed:
        for control in args.control:
            print(json.dumps(run_control(cell, seed, control)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
