"""Runner for training cells: ``engine.train_batch`` step after step, as a
user's loop calls it, over a ring of batches made from the seed.

The window adds no waiting of its own: ``train_batch`` ends every step
with the throughput timer's device sync (``utils/timer.py``), so the
interval between two returns is a step time and the clock is read only
where the device is known to be done. (The loop holds at most two steps in
flight, which today never waits.) Throughput is all tokens of the whole
steps finished over the whole window: the mean, so that a change which
makes every tenth step slow is seen.
"""

import time

import numpy as np

from benchmarks.lib import harness, stats
from benchmarks.lib.traffic import train_ring


def _engine(cell, env, model):
    import deepspeed_tpu
    from deepspeed_tpu.parallel.topology import MeshTopology

    dep = cell.config["train"]
    batch = int(cell.traffic["seqs_per_chip"]) * cell.chips
    ds_config = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": int(dep.get("gradient_accumulation_steps", 1)),
        "optimizer": dep["optimizer"],
        "bf16": {"enabled": dep["dtype"] == "bfloat16"},
        "gradient_clipping": dep["gradient_clipping"],
        "zero_optimization": {"stage": dep["zero_stage"]},
        "steps_per_print": 10 ** 9,
        "seed": env.seed31,
    }
    mesh = dict(dep["mesh"])
    topology = MeshTopology(devices=list(env.devices), **mesh)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config, topology=topology)
    return engine


def _model(cell, family):
    import jax.numpy as jnp

    dep = cell.config["train"]
    tokens = int(cell.traffic["seqs_per_chip"]) * cell.chips * int(cell.traffic["seq_len"])
    return family.model(
        cell.config, dep, n_positions=int(cell.traffic["seq_len"]), remat=dep["remat"],
        attention_backend=dep["attention_backend"],
        dtype=jnp.bfloat16 if dep["dtype"] == "bfloat16" else jnp.float32,
        fused_head_loss_chunk=min(int(dep["fused_head_loss_chunk"]), tokens))


def _check_against_reference(cell, family, engine, batch):
    """The reference's loss (and, where the configuration asks, gradient
    norm) at the weights the engine holds now, on ``batch``: what the
    engine's next step on that batch must reproduce."""
    from jax.sharding import NamedSharding

    check = cell.config["train"]["reference_check"]
    flat = family.to_reference(engine.state.params)
    ids = batch["input_ids"]
    n_head = cell.config["n_head"]
    place = NamedSharding(engine.mesh, engine.topology.batch_spec())
    want = {"loss": family.reference_loss(flat, ids, n_head,
                                          check["seqs_per_call"] * cell.chips, place)}
    if check.get("grad_norm_rtol") is not None:
        want["grad_norm"] = family.reference_grad_norm(flat, ids, n_head,
                                                       check["seqs_per_call"])
    return want


def run(cell, env):
    import jax

    from envutil import use_compile_cache

    setup, traffic = env.setup, cell.traffic
    family = cell.family
    import deepspeed_tpu  # noqa: F401
    setup.mark("imports")
    cache_dir = use_compile_cache()
    vocab = cell.config["train"].get("vocab_rows", cell.config["vocab_size"])
    ring = train_ring(traffic, vocab, env.seed, cell.chips)
    engine = _engine(cell, env, _model(cell, family))
    engine.initialize_state(ring[0])
    jax.block_until_ready(engine.state.params)
    setup.mark("engine_and_weights")

    # warm-up: the first step compiles (or loads from the cache); the
    # timer's syncs start at the second; stop once a step compiles nothing
    with harness.compiles() as warm:
        engine.train_batch(ring[0])
    setup.mark("first_step_compile_or_cache_load")
    step = 1
    while True:
        with harness.compiles() as seen:
            engine.train_batch(ring[step % len(ring)])
        step += 1
        if step >= int(traffic["min_warmup_steps"]) and not seen:
            break
        if step > 20:
            raise harness.BenchmarkError(f"still compiling after 20 warm-up steps: {seen}")
    jax.block_until_ready(engine.state.params)
    # the engine's own peak, and the one the last line reports: read before the
    # reference check, whose fp32 gradient beside the engine's state is the
    # larger. Every later step runs the program these steps ran
    step_peak = harness.memory_peak_bytes(env.devices)
    setup.mark("warm_up")

    # the reference at the weights the engine holds now, then the step that
    # must reproduce its loss and gradient norm
    check_batch = ring[step % len(ring)]
    want = _check_against_reference(cell, family, engine, check_batch)
    got = {"loss": float(engine.train_batch(check_batch)),
           "grad_norm": engine.get_global_grad_norm()}
    step += 1
    setup.mark("reference_check")

    check = cell.config["train"]["reference_check"]
    agree = {}
    for name, ref_value in want.items():
        rel = abs(got[name] - ref_value) / abs(ref_value)
        agree[name] = {"engine": got[name], "reference": ref_value, "rel_diff": rel,
                       "rtol": check[name + "_rtol"]}
    correct = all(a["rel_diff"] <= a["rtol"] for a in agree.values())
    harness.log(reference_check=agree, compile_cache=cache_dir, engine_peak_bytes=step_peak,
                compiled_in_warm_up=[[n, round(s, 2)] for n, s in warm if s >= 0.5])

    tokens_per_step = int(traffic["seqs_per_chip"]) * cell.chips * int(traffic["seq_len"])
    # a traced run traces the last ``trace_seconds`` of the window, so that
    # the profiler's start and stop (seconds each) fall outside the steps the
    # host-clock numbers are taken from
    trace_from_s = env.seconds - float(traffic["trace_seconds"]) if env.trace else None
    losses, stamps, untraced = [], [], None
    setup.close()
    with harness.quiet_host(), harness.compiles() as in_window:
        t_start = t_now = time.perf_counter()
        while t_now - t_start < env.seconds:
            if env.trace and untraced is None and t_now - t_start >= trace_from_s:
                jax.block_until_ready(losses)
                untraced = len(stamps)
                env.tracer.start()
            if len(losses) >= 2:
                # at most two steps in flight. Free while train_batch ends in
                # the timer's sync (the loss is ready long since); it keeps
                # the window its length should the engine stop waiting
                jax.block_until_ready(losses[-2])
            with env.tracer.span("train_batch"):
                losses.append(engine.train_batch(ring[step % len(ring)]))
            t_now = time.perf_counter()
            stamps.append(t_now)
            step += 1
        jax.block_until_ready(losses[-1])
        t_end = time.perf_counter()
        env.tracer.stop()
    with env.tracer.span("read_loss"):
        losses = np.asarray(jax.device_get(losses), np.float32)

    finite = np.isfinite(losses)
    steps = len(losses)
    window_s = t_end - t_start
    tok_s_chip = steps * tokens_per_step / window_s / cell.chips
    # step intervals of the part of the window the profiler was off in
    edges = ([t_start] + stamps)[:None if untraced is None else untraced + 1]
    intervals = [(b - a) * 1e3 for a, b in zip(edges, edges[1:])]
    if not intervals:       # a window shorter than the traced slice
        intervals = [(b - a) * 1e3 for a, b in zip([t_start] + stamps, stamps)]
    traced_steps = 0 if untraced is None else steps - untraced
    harness.log(window_s=window_s, steps=steps, tokens_per_step=tokens_per_step,
                step_ms_p50=stats.percentile(intervals, 50),
                step_ms_p99=stats.percentile(intervals, 99), step_ms_max=max(intervals),
                slow_steps=[[i, ms] for i, ms in enumerate(intervals)
                            if ms > 1.25 * stats.percentile(intervals, 50)][:10],
                first_loss=float(losses[0]), last_loss=float(losses[-1]),
                compiled_in_window=in_window)
    correct = correct and bool(finite.all()) and not in_window
    return {
        "correct": correct, "attempted": steps, "failed": int((~finite).sum()),
        "memory_peak_bytes": step_peak,
        "end_to_end": {"train_tok_s_chip": tok_s_chip},
        "spans": {"train_step_ms": intervals},
        "counters": {"recompiles_in_window": len(in_window),
                     "train_tok_s_chip_steady": (tokens_per_step * len(intervals) * 1e3
                                                 / sum(intervals) / cell.chips),
                     "tokens_per_step": tokens_per_step, "traced_steps": traced_steps,
                     "step_peak_bytes": step_peak},
    }
