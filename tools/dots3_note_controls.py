"""Controls for a serving cell's reference check (``--workload``; the
dots3-note-prev cell's by default, the Laguna cell's with
``--workload serve-laguna-xs2-mixedlen-sat --control program fp8_weights
full_window``, the Ouro cell's with ``--workload serve-ouro-2.6b-mathword-sat
--control program fp8_weights three_passes shared_pass_cache``, the
DeepSeek-V3.2 cell's with ``--workload serve-deepseek-v3.2-ctx32k-sat --control
program fp8_weights last_positions flat_top_k plain_rope``): does the comparison that decides ``correct`` refuse a server
computed below the precision the configuration states, and one that attends the
WRONG positions?

As ``tools/joyai_llm_flash_controls.py``: each control stands **in the
program's place**, a server built exactly as the cell builds it
(``benchmarks/runners/serve.py::_server``) with one thing changed, serving the
cell's two checked requests through chunked prefill and decode, then held to
the plain reference over the configuration's own weights by the runner's own
``_compare_with_reference``: the ``ok`` printed is the ``correct`` the cell
would have reported for that server.

* ``program``: the server as it is.
* ``fp8_weights``: the server's matrices rounded to float8 e4m3 (and back to
  the served type): the nearest precision below the stated bfloat16.
* ``last_positions``: the full layers attend the LAST ``index_topk``
  positions at or before a query in place of the indexer's choice (both
  ticks: the package's ``kth_largest`` is handed each position's number for
  its score). Every other number is the program's: what this control moves is
  which 2,048 of up to 6,128 latents a query reads, and nothing else.
* ``full_window`` (a ``models/llama.py`` family with window layers, Laguna):
  the sliding layers attend EVERY earlier position, over pools of the full
  layers' extent in place of their rings (the family's ``model`` is handed
  ``sliding_window`` = the slot's capacity and no ``window_ring``; the layers
  keep their own heads and their own RoPE). Five pools of every position do
  not fit beside the weights at the cell's 32 slots, so this control's server
  has 8: the two checked requests' arithmetic does not see the slot count.
* ``three_passes`` (a ``models/llama.py`` family with a looped stack, Ouro): the
  server runs ``total_ut_steps`` - 1 passes of its stack (the family's
  ``model`` is handed ``loop_passes`` one short; the weights and the head are
  the configuration's): a pass left out, whatever else it did.
* ``shared_pass_cache`` (the same families): every pass writes and reads pass
  1's pool (the package's ``_cache_of_pass`` hands every call part 0 of the
  layer's pools): at a position a pass finds its own keys, at every earlier one
  what the LAST pass of that token left there: the one cache shared by the
  passes that arXiv:2510.25741 measures as an approximation, and that this
  configuration does not make.

* ``flat_top_k`` (an indexed latent family whose router has groups,
  DeepSeek-V3.2): the router takes the flat top ``k`` of all its experts in
  place of the group-limited choice (the family's ``model`` is handed
  ``n_group`` = ``topk_group`` = 1; weights, bias and scale are the
  configuration's).
* ``plain_rope`` (the same family): RoPE turns by ``theta`` alone and the
  softmax scale is ``1 / sqrt(192)``, in place of YaRN's blended frequencies
  and ``mscale^2`` (the family's ``model`` is handed ``rope_scaling`` None).

``fp8_weights`` applies to every family; ``last_positions`` to an indexed
latent family (dots3-note), ``full_window`` to a llama family with window
layers (Laguna), ``three_passes`` and ``shared_pass_cache`` to a llama family
with a looped stack (Ouro).

Each line also carries what the checked requests' ticks chose
(``selected_pct``: positions attended over positions live on the full
layers, the program's device-side counts).

    python3 tools/dots3_note_controls.py --seed <n> [<n> ...] [--control <name> ...]

``--draw embed_tokens=128 routed_down_proj=0.125`` overrides the configuration's
seeded draw's multipliers (a sweep before one is written into the file).
Prints one JSON line a seed and control. Runs on whatever device JAX finds;
the numbers that count are the chip's.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CONTROLS = ("program", "fp8_weights", "last_positions", "full_window", "three_passes",
            "shared_pass_cache", "flat_top_k", "plain_rope")
WORKLOAD = "serve-dots3-note-prev-longctx-sat"


@contextlib.contextmanager
def last_positions_chosen():
    """The package's selection with every position's own number for its
    index score: the ``top_k`` largest are the last ``top_k``. Both forms of
    the selection are handed the numbers: XLA's (``kth_largest``) and, since
    PR 38 the one a chip runs, the kernel's (``sparse_select.select_top_k``:
    until PR 58 this control changed the first alone and read the program's
    own gap on the chip)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import deepseek_v3 as package
    from deepspeed_tpu.ops.pallas import sparse_select

    plain, plain_kernel = package.kth_largest, sparse_select.select_top_k

    def places_of(scores):
        return jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)

    package.kth_largest = lambda scores, valid, k: plain(places_of(scores), valid, k)
    sparse_select.select_top_k = lambda scores, *rest, **kw: plain_kernel(
        places_of(scores), *rest, **kw)
    try:
        yield
    finally:
        package.kth_largest, sparse_select.select_top_k = plain, plain_kernel


def full_window_family(family):
    """``family`` whose model's window layers attend every earlier position."""
    import types

    def model(config, deployment):
        return family.model(config, deployment, sliding_window=deployment["max_out_tokens"],
                            window_ring=None)

    return types.SimpleNamespace(model=model)


def three_passes_family(family):
    """``family`` whose model runs one pass fewer than the configuration's."""
    import types

    def model(config, deployment):
        return family.model(config, deployment, loop_passes=int(config["total_ut_steps"]) - 1)

    return types.SimpleNamespace(model=model)


def overridden_family(**overrides):
    """``family -> family`` whose model is built with ``overrides`` over the
    configuration's sizes."""
    import types

    def stand_in(family):
        def model(config, deployment):
            return family.model(config, deployment, **overrides)

        return types.SimpleNamespace(model=model)

    return stand_in


@contextlib.contextmanager
def one_cache_for_every_pass():
    """The package's looped stack with every pass handed pass 1's part of its
    layer's pools: written by each pass in turn, read by each as it stands."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import llama as package

    plain = package._cache_of_pass

    def first_part(cfg, loop_pass):
        given = plain(cfg, loop_pass)
        return dict(given, part=jnp.zeros_like(given["part"])) if given else given

    package._cache_of_pass = first_part
    try:
        yield
    finally:
        package._cache_of_pass = plain


def run_control(cell, seed, control):
    """One server, one comparison: the line's fields."""
    import copy

    import jax
    import jax.numpy as jnp
    from benchmarks.lib import harness
    from deepspeed_tpu.utils import trace
    from nemotron_h_controls import fp8_family

    family, runner = cell.family, cell.runner
    if control == "full_window":
        cell = copy.copy(cell)
        cell.config = copy.deepcopy(cell.config)
        cell.config["serve"]["slots"] = min(8, cell.config["serve"]["slots"])
    gc.collect()    # an earlier control's server: 8.2 GB of weights and 3.1 of cache do not fit twice
    t0 = time.time()
    env = harness.Env(seed, 0, 0, harness.Setup(t0), jax.devices()[:1], harness.Tracer(False, ""))
    before = dict(trace.recorder().counters)
    changed = {"last_positions": last_positions_chosen,
               "shared_pass_cache": one_cache_for_every_pass}.get(control, contextlib.nullcontext)()
    stand_in = {"fp8_weights": fp8_family, "full_window": full_window_family,
                "three_passes": three_passes_family,
                "flat_top_k": overridden_family(n_group=1, topk_group=1),
                "plain_rope": overridden_family(rope_scaling=None)}.get(
                    control, lambda family: family)
    with changed:       # the programs are traced in warm-up, under the change
        engine, sched = runner._server(cell, env, stand_in(family))
        sched.warmup()
        reqs = runner._checked_requests(cell, env, sched)
    counted = {k: v - before.get(k, 0) for k, v in trace.recorder().counters.items()}
    line = {"seed": seed, "control": control, "draw": cell.config.get("draw"),
            "tokens_emitted_distinct": len({int(t) for r in reqs for t in r.output})}
    live = sum(counted.get(f"dsa_positions_live_{kind}", 0) for kind in ("prefill", "decode"))
    if live:
        line["selected_pct"] = 100.0 * sum(counted.get(f"dsa_positions_selected_{kind}", 0)
                                           for kind in ("prefill", "decode")) / live
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
    parser.add_argument("--control", nargs="+", default=list(CONTROLS[:3]), choices=CONTROLS)
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--draw", nargs="*", default=[], metavar="LEAF=MULTIPLIER",
                        help="the configuration's `draw` with these multipliers, for a sweep")
    args = parser.parse_args(argv)

    from benchmarks.lib import harness
    from envutil import use_compile_cache

    use_compile_cache()
    cell = harness.Cell(args.root, harness.load_json(args.root, "BENCHMARK.json"), args.workload)
    for leaf, by in (pair.split("=") for pair in args.draw):
        cell.config["draw"][leaf] = float(by)
    for seed in args.seed:
        for control in args.control:
            print(json.dumps(run_control(cell, seed, control)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
