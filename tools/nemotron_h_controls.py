"""Controls for the Nemotron-H serving cell's reference check: does the
comparison that decides ``correct`` refuse a server computed below the
precision the configuration states?

Each control stands **in the program's place**: a server is built exactly
as the cell builds it (``benchmarks/runners/serve.py::_server``), with one
thing lowered, serves the cell's two checked requests through chunked
prefill and decode (``_checked_requests``), and is then held to the plain
reference over the configuration's own weights by the runner's own
``_compare_with_reference``: the ``ok`` printed is the ``correct`` the cell
would have reported for that server.

* ``program``: the server as it is.
* ``fp8_weights``: the server's matrices rounded to float8 e4m3 (and back
  to the served type): the nearest precision below the stated bfloat16.
* ``bf16_state``: the recurrent state keeps bfloat16's 8 mantissa bits
  after every prefill chunk and every decode step (the configuration
  states float32).

Beside the runner's statistic (which sees emitted tokens only) each line
carries ``state_rel_err``: the checked slots' carried ``ssm_state`` against
the reference's final state (``families/nemotron_h.py::
reference_final_states``), the largest over layers, slots and heads of
``|served - reference|`` over ``|reference|`` (2-norms over a head's
state), and ``first_layer_state_rel_err``, the same over the first Mamba
layer alone, which is what tells a bfloat16 state on the chip (0.009-0.011
as served, 0.042-0.052 with the state rounded, 0.15-0.18 with fp8 weights,
three seeds; deeper layers inherit the stream's bfloat16 error and read
0.05-0.11 either way). No runner reads it yet (``PERF.md`` sections 6 and
7, PR 30).

    python3 tools/nemotron_h_controls.py --seed <n> [<n> ...] [--control <name> ...]

Prints one JSON line a seed and control. Runs on whatever device JAX finds;
the numbers that count are the chip's.
"""

import argparse
import contextlib
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = ("program", "fp8_weights", "bf16_state")


def fp8_family(family):
    """``family`` whose model's seeded weights are rounded to float8 e4m3:
    every leaf of two or more axes, and back to the type it is served in.
    The runner makes the weights inside one jitted program, where the TPU's
    compiler removes a cast there and back as excess precision (it read
    ``weights_are_fp8_values`` false, and the server unchanged): a barrier
    between the two casts keeps both."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        if w.ndim < 2:
            return w
        return jax.lax.optimization_barrier(w.astype(jnp.float8_e4m3fn)).astype(w.dtype)

    def model(config, deployment):
        plain = family.model(config, deployment)

        class Fp8Weights(type(plain)):
            def init(self, *args, **kwargs):
                return jax.tree.map(rounded, super().init(*args, **kwargs))

        return Fp8Weights(plain.config)

    return types.SimpleNamespace(model=model)


@contextlib.contextmanager
def bf16_state():
    """The package's two forms of the recurrence with the state they return
    rounded to bfloat16's mantissa (``reduce_precision``: a cast there and
    back is removed by the TPU's compiler as excess precision). The leaf
    stays float32 and holds bfloat16 values: a server that kept its state
    in bfloat16, whatever else it did."""
    import jax
    from deepspeed_tpu.models import nemotron_h as package

    def rounding(fn):
        def wrapped(*args, **kwargs):
            y, state = fn(*args, **kwargs)
            return y, jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return wrapped

    kept = package.ssd_chunk_scan, package.ssm_step
    package.ssd_chunk_scan, package.ssm_step = rounding(kept[0]), rounding(kept[1])
    try:
        yield
    finally:
        package.ssd_chunk_scan, package.ssm_step = kept


def carried_states(sched, n):
    """The first ``n`` slots' ``ssm_state`` on the host, a Mamba layer each
    (a fresh scheduler hands out its slots in order: slot ``b`` is the
    ``b``-th checked request's)."""
    import jax
    import numpy as np
    from deepspeed_tpu.inference.serving.programs import _leaf_name

    return [np.asarray(leaf[:n], np.float32)
            for path, leaf in jax.tree_util.tree_flatten_with_path(sched._cache)[0]
            if _leaf_name(path) == "ssm_state"]


def state_rel_err(served, reference):
    """``|served - reference|`` over ``|reference|``, 2-norms over one
    head's state, the worse of the slots: ``(the largest over layers and
    heads, [largest, median, least over a layer's heads] a layer)``. A head
    that forgets fast carries its last few tokens, whose inputs already
    differ by the server's bfloat16 arithmetic; a head that forgets slowly
    averages that out and accumulates what rounds its state."""
    import numpy as np

    norm = lambda t: np.sqrt(np.square(t).sum(axis=(-2, -1)))  # noqa: E731
    by_layer = []
    for got, want in zip(served, reference, strict=True):
        want = np.asarray(want, np.float32)
        heads = (norm(got.reshape(want.shape) - want) / norm(want)).max(axis=0)
        by_layer.append([float(heads.max()), float(np.median(heads)), float(heads.min())])
    return max(layer[0] for layer in by_layer), by_layer


def run_control(cell, seed, control):
    """One server, one comparison: the line's fields."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import harness

    family, runner = cell.family, cell.runner
    gc.collect()    # an earlier control's server: 9.3 GB of weights do not fit twice
    t0 = time.time()
    env = harness.Env(seed, 0, 0, harness.Setup(t0), jax.devices()[:1], harness.Tracer(False, ""))
    served_by = fp8_family(family) if control == "fp8_weights" else family
    with bf16_state() if control == "bf16_state" else contextlib.nullcontext():
        engine, sched = runner._server(cell, env, served_by)
        sched.warmup()
        reqs = runner._checked_requests(cell, env, sched)
    line = {"seed": seed, "control": control}
    served = carried_states(sched, len(reqs))
    if control == "fp8_weights":
        # the rounding was made inside a jitted program: show that it was made
        # (rounding again changes nothing), then let the reference read the
        # configuration's own weights, not this server's
        head = family.to_reference(engine.params)["head"]
        line["weights_are_fp8_values"] = bool(
            (head.astype(jnp.float8_e4m3fn).astype(head.dtype) == head).all())
        del engine, sched, head
        gc.collect()
        engine, sched = runner._server(cell, env, family)
    del sched
    gc.collect()
    ids = np.stack([np.concatenate([r.prompt, np.asarray(r.output, np.int32)])[:-1] for r in reqs])
    line.update(runner._compare_with_reference(cell, family, engine, reqs))
    states = family.reference_final_states(family.to_reference(engine.params), ids)
    worst, by_layer = state_rel_err(served, states)
    # the first Mamba layer reads the embeddings themselves: what its state
    # is off by is the server's own arithmetic and nothing handed down
    line.update(state_rel_err=worst, first_layer_state_rel_err=by_layer[0][0],
                state_rel_err_by_layer=by_layer,
                device=jax.devices()[0].device_kind, seconds=round(time.time() - t0, 1))
    return line


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="serve-nemotron-3-super-reason-sat")
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
