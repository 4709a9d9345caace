"""Controls for the SmallThinker training cell's reference check: does the
comparison that decides ``correct`` refuse a step computed below the precision
the configuration states, and one that attends the WRONG positions?

Each control stands **in the program's place** and is held **by the runner
itself**: ``benchmarks/runners/train.py::run`` is called as the cell calls it
(its ``_model`` / ``_engine``, the cell's ring, its warm-up steps, its
``_check_against_reference``, its own comparison of the loss and of the
gradient's norm with their limits), over a window of one step, with one thing
changed from outside. The ``ok`` printed is the ``correct`` of the object the
runner returned; the numbers beside it are the runner's own
``reference_check`` line.

* ``program``: nothing changed.
* ``fp8_experts``: the held experts' weights (gate, up, down of every layer)
  rounded to float8 e4m3 and back, in the engine's masters, AFTER the
  reference has read them (the runner's ``_check_against_reference`` is
  wrapped: it returns what it returned, and the engine then holds the rounded
  weights): the nearest precision below the stated bfloat16.
* ``full_window``: the program's window layers attend every earlier position
  (the runner's ``_model`` is handed ``sliding_window_layout`` all zero; the
  reference keeps the configuration's windows). Every other number is the
  program's.

    python3 tools/smallthinker_controls.py --seed <n> [<n> ...] [--control <name> ...]

Prints one JSON line a seed and control. Runs on whatever device JAX finds;
the numbers that count are the chip's.
"""

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = ("program", "fp8_experts", "full_window")
WORKLOAD = "train-smallthinker-21b-a3b-seq16k"


def _fp8_experts(engine):
    """The engine's state with every expert bank leaf at float8 e4m3 values."""
    import jax
    import jax.numpy as jnp

    def rounded(path, leaf):
        if "deepspeed_experts" not in jax.tree_util.keystr(path):
            return leaf
        # the barrier keeps both casts: the TPU's compiler removes a cast
        # there and back as excess precision (tools/nemotron_h_controls.py)
        return jax.lax.optimization_barrier(leaf.astype(jnp.float8_e4m3fn)).astype(leaf.dtype)

    params = jax.jit(lambda p: jax.tree_util.tree_map_with_path(rounded, p),
                     donate_argnums=0)(engine.state.params)
    return engine.state._replace(params=params)


def run_control(cell, seed, control):
    """``runners/train.py::run`` over a one-step window with ``control`` in
    the program's place; returns the runner's verdict and its own
    ``reference_check`` numbers."""
    import contextlib
    import io
    import time

    import jax

    runner = cell.runner
    harness = runner.harness        # the module whose ``log`` the runner calls
    devices = jax.devices()[:cell.chips]
    env = harness.Env(seed, 1e-3, False, harness.Setup(time.time()), devices,
                      harness.Tracer(False, None))
    logged, patched = {}, {"log": harness.log, "_model": runner._model,
                           "_check_against_reference": runner._check_against_reference}
    harness.log = lambda **fields: logged.update(fields)

    if control == "full_window":
        def model(cell_, family_):
            altered = copy.copy(cell_)
            altered.config = dict(cell_.config, sliding_window_layout=[0] * cell_.config[
                "num_hidden_layers"])
            built = patched["_model"](altered, family_)
            # the reference is the configuration's own, whatever program was built
            family_._built["spec"] = family_.spec_of(cell_.config, int(cell_.traffic["seq_len"]))
            return built
        runner._model = model
    if control == "fp8_experts":
        def check(cell_, family_, engine, batch):
            want = patched["_check_against_reference"](cell_, family_, engine, batch)
            engine.state = _fp8_experts(engine)
            import jax.numpy as jnp
            bank = engine.state.params["layers_0"]["moe"]["deepspeed_moe"]["experts"][
                "deepspeed_experts"]["down_proj"]["kernel"]
            logged["weights_are_fp8_values"] = bool(
                (bank.astype(jnp.float8_e4m3fn).astype(bank.dtype) == bank).all())
            return want
        runner._check_against_reference = check
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            result = runner.run(cell, env)
    finally:
        harness.log = patched["log"]
        runner._model, runner._check_against_reference = (
            patched["_model"], patched["_check_against_reference"])
    line = {"seed": seed, "control": control, "ok": result["correct"],
            "failed_steps": result["failed"], **logged["reference_check"]}
    for key in ("weights_are_fp8_values", "compiled_in_window"):
        if key in logged:
            line[key] = logged[key]
    if result["memory_peak_bytes"] is not None:
        line["memory_peak_bytes"] = result["memory_peak_bytes"]
    gc.collect()
    return line


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default=WORKLOAD)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--control", nargs="+", default=list(CONTROLS), choices=CONTROLS)
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--draw", help="JSON: the seeded draw's multipliers, in place of the "
                                       "configuration's `draw` (a sweep, before a limit is set)")
    args = parser.parse_args(argv)

    from benchmarks.lib import harness
    from envutil import use_compile_cache

    use_compile_cache()
    cell = harness.Cell(args.root, harness.load_json(args.root, "BENCHMARK.json"), args.workload)
    if args.draw is not None:
        cell.config["draw"] = json.loads(args.draw)
    for seed in args.seed:
        for control in args.control:
            print(json.dumps(dict(run_control(cell, seed, control), draw=cell.config["draw"])),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
