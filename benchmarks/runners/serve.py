"""Runner for serving cells: one process, the continuous-batching
scheduler driven by this loop on the scheduler's injected clock. Submit
what is due (stamped with the time it was *due*), ``sched.step()``, repeat.

A pre-roll of the same traffic fills the slots before the window opens.
The window opens and closes on a tick boundary and its length is measured.
After it a drain lets the requests that were due inside it reach their
first token; arrivals go on through the drain, as they would.
"""

import collections
import time

import numpy as np

from benchmarks.lib import harness, stats
from benchmarks.lib.traffic import serve_schedule


def _server(cell, env, family):
    """The engine and scheduler a deployment builds, weights from the seed
    made on the device in one call, in the type they are served in."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler, ServingConfig
    from deepspeed_tpu.parallel.topology import MeshTopology

    dep = cell.config["serve"]
    # the queue's limit is the deployment's; a configuration without the key
    # keeps the server's default
    limit = {"max_queue": int(dep["max_queue"])} if "max_queue" in dep else {}
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dep["dtype"]]
    model = family.model(cell.config, dep)

    def weights(key):
        params = nn.meta.unbox(model.init(key, jnp.zeros((1, 8), jnp.int32))["params"])
        return jax.tree.map(lambda p: p.astype(dtype), params)

    params = jax.jit(weights)(jax.random.PRNGKey(env.seed31))
    engine = deepspeed_tpu.init_inference(
        model, params=params, dtype=dtype, replace_with_kernel_inject=True,
        max_out_tokens=dep["max_out_tokens"], topology=MeshTopology(devices=list(env.devices)))
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=dep["slots"], page_size=dep["page_size"], kv_quant=dep["kv_quant"],
        prefill_chunk=dep["prefill_chunk"], prefill_interleave=dep["prefill_interleave"],
        prefix_cache=dep["prefix_cache"], **limit),
        clock=time.perf_counter)
    return engine, sched


def _checked_requests(cell, env, sched):
    """Two seeded requests through chunked prefill and decode over the
    scheduler's cache, in set-up; :func:`_compare_with_reference` holds
    what they emitted against the reference once the window is over."""
    from deepspeed_tpu.inference.serving import Request

    check = cell.config["serve"]["reference_check"]
    rng = np.random.default_rng([env.seed, 1])   # not the schedule's stream: no shared prefix
    reqs = [Request(prompt=rng.integers(0, cell.config["vocab_size"],
                                        (int(check["prompt_len"]),)).astype(np.int32),
                    max_new_tokens=int(check["max_new_tokens"])) for _ in range(2)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    return reqs


def _compare_with_reference(cell, family, engine, reqs):
    """The reference's full forward pass over prompt + emitted tokens: every
    emitted token's reference logit must lie within ``logit_gap_tol`` of
    the reference's maximum at its position. Runs after the window, so that
    the memory peak read at its close is the server's alone (the reference
    holds an fp32 copy of the weights)."""
    check = cell.config["serve"]["reference_check"]
    n_prompt, n_new = int(check["prompt_len"]), int(check["max_new_tokens"])
    if any(len(r.output) != n_new for r in reqs):
        return {"worst_logit_gap": None, "tol": check["logit_gap_tol"], "ok": False}
    ids = np.stack([np.concatenate([r.prompt, np.asarray(r.output, np.int32)])[:-1] for r in reqs])
    flat = family.to_reference(engine.params)
    logits = np.asarray(family.reference_logits(flat, ids, cell.config["n_head"]), np.float32)
    worst = 0.0
    for b, r in enumerate(reqs):
        at = logits[b, n_prompt - 1:]
        gap = at.max(axis=-1) - at[np.arange(n_new), np.asarray(r.output)]
        worst = max(worst, float(gap.max()))
    ok = bool(np.isfinite(logits).all()) and worst <= check["logit_gap_tol"]
    return {"worst_logit_gap": worst, "tol": check["logit_gap_tol"], "ok": ok}


def run(cell, env):
    from envutil import use_compile_cache

    setup, traffic = env.setup, cell.traffic
    family = cell.family
    import deepspeed_tpu  # noqa: F401
    from deepspeed_tpu.inference.serving import Request
    setup.mark("imports")
    cache_dir = use_compile_cache()
    engine, sched = _server(cell, env, family)
    setup.mark("engine_and_weights")
    with harness.compiles() as warm:
        sched.warmup()
    setup.mark("compile_or_cache_load")
    checked = _checked_requests(cell, env, sched)
    setup.mark("checked_requests")
    harness.log(compile_cache=cache_dir, slots=sched.slots,
                compiled_in_warm_up=[[n, round(s, 2)] for n, s in warm if s >= 0.5])

    preroll_s, drain_s = float(traffic["preroll_s"]), float(traffic["drain_s"])
    schedule = collections.deque(serve_schedule(
        traffic, cell.config["vocab_size"], env.seed, preroll_s + env.seconds + drain_s + 1.0))
    scheduled = len(schedule)
    # a traced run traces the last ``trace_seconds`` of the window; its
    # host-clock numbers come from the part before, which the profiler's
    # start and stop (seconds each) do not touch
    trace_from_s = env.seconds - float(traffic["trace_seconds"]) if env.trace else None
    clock, tracer = time.perf_counter, env.tracer
    requests, ticks = [], []   # ticks: (kind, seconds, slots busy, live positions) in the window

    def progress():
        return sum(r.prefill_pos + len(r.output) for r in requests)

    def kv_live():
        """Cache positions that hold a token now, over all slots."""
        return sum(r.prefill_pos + len(r.output) for r in sched.in_flight)

    def submit_due(now):
        while schedule and schedule[0]["due"] <= now - t_origin:
            item = schedule.popleft()
            req = Request(prompt=item["prompt"], max_new_tokens=item["max_new_tokens"],
                          arrival_time=t_origin + item["due"])
            req.meta["submitted"] = now
            sched.submit(req)
            requests.append(req)

    def tick():
        """Submit what is due and run one scheduler tick; returns
        (kind, seconds, slots busy in it, live cache positions, clock after)."""
        before = clock()
        with tracer.span("submit"):
            submit_due(before)
        done = len(sched.finished)
        with tracer.span("sched_step"):
            kind = sched.step()
        after = clock()
        if kind == "idle":
            with tracer.span("idle_wait"):
                wait = (t_origin + schedule[0]["due"] - after) if schedule else 0.001
                time.sleep(min(max(wait, 0.0), 0.001))
        busy = len(sched.in_flight) + len(sched.finished) - done
        return kind, after - before, busy, kv_live(), after

    t_origin = clock()
    now = t_origin
    while now - t_origin < preroll_s:           # pre-roll: set-up the traffic needs
        now = tick()[-1]
    setup.mark("pre_roll")
    setup.close()

    untraced, t_traced, queue_mid = None, None, None
    with harness.quiet_host(), harness.compiles() as in_window:
        t_open = now = clock()
        progress_open = progress()
        while now - t_open < env.seconds:
            if env.trace and untraced is None and now - t_open >= trace_from_s:
                untraced, t_traced = len(ticks), now
                tracer.start()
            kind, secs, busy, live, now = tick()
            ticks.append((kind, secs, busy, live))
            if queue_mid is None and now - t_open >= env.seconds / 2:
                queue_mid = len(sched.queue)
        t_close = now
        progress_close = progress()
        queue_close = len(sched.queue)
        tracer.stop()       # every tick ends with its tokens read back: the device is done
    t_host = t_close if t_traced is None else t_traced   # host-clock numbers end here
    server_peak = harness.memory_peak_bytes(env.devices)   # before the reference runs

    def in_window_due(r):
        return t_open <= r.arrival_time < t_close

    waiting = [r for r in requests if in_window_due(r)]
    while now - t_close < drain_s and any(
            r.first_token_time is None and not r.done for r in waiting):
        now = tick()[-1]
    t_drained = now
    agree = _compare_with_reference(cell, family, engine, checked)

    window_s = t_close - t_open
    gaps = [(b - a) * 1e3 for r in requests
            for a, b in zip(r.token_times, r.token_times[1:]) if t_open < b <= t_close]
    no_first_token = [r for r in waiting if r.first_token_time is None]
    ttfts = [((r.first_token_time if r.first_token_time is not None else t_drained)
              - r.arrival_time) * 1e3 for r in waiting]
    late = [(r.meta["submitted"] - r.arrival_time) * 1e3 for r in waiting
            if r.arrival_time < t_host]
    # the per-layer time to first token of a traced run: requests whose whole
    # drain had passed before the profiler started (its start stalls the loop)
    ttfts_untraced = [t for t, r in zip(ttfts, waiting)
                      if t_traced is None or r.arrival_time < t_traced - drain_s]
    touched = [r for r in requests if r.arrival_time < t_close
               and not (r.finish_time is not None and r.finish_time < t_open)]
    refused = [r for r in touched if r.state == "refused"]
    failed = len({id(r) for r in refused + no_first_token})
    completed = [r for r in requests if r.finish_time is not None
                 and t_open < r.finish_time <= t_close]

    measured = ticks[:untraced]
    by_kind = collections.Counter(t[0] for t in measured)
    working = [t for t in measured if t[0] != "idle"]
    end_to_end = {"serve_total_tok_s": (progress_close - progress_open) / window_s}
    if gaps:
        end_to_end["itl_p95_ms"] = stats.percentile(gaps, 95)
    harness.log(reference_check=agree, server_peak_bytes=server_peak)
    harness.log(window_s=window_s, ticks=dict(collections.Counter(t[0] for t in ticks)),
                requests_due_in_window=len(waiting), completed_in_window=len(completed),
                completed_per_s=len(completed) / window_s, offered_per_s=len(waiting) / window_s,
                queue_at_middle=queue_mid, queue_at_close=queue_close, requests_scheduled=scheduled,
                kv_live_pct=100.0 * sum(t[3] for t in ticks) / max(1, len(ticks)) / (
                    sched.slots * int(cell.config["serve"]["max_out_tokens"])),
                in_flight_at_close=len(sched.in_flight),
                no_first_token_after_drain=len(no_first_token), refused=len(refused),
                itl_gaps=len(gaps), itl_p50_ms=stats.percentile(gaps, 50),
                ttft_p50_ms=stats.percentile(ttfts, 50), ttft_p90_ms=stats.percentile(ttfts, 90),
                ttft_max_ms=max(ttfts, default=None),
                gen_late_max_ms=max(late, default=None), compiled_in_window=in_window,
                **end_to_end)
    spans = {"decode_tick_ms": [t[1] * 1e3 for t in measured if t[0] == "decode"],
             "prefill_tick_ms": [t[1] * 1e3 for t in measured if t[0] == "prefill"],
             "gen_late_ms": late, "ttft_ms": ttfts_untraced}
    counters = {"recompiles_in_window": len(in_window),
                "queue_at_close": queue_close, "requests_scheduled": scheduled,
                "prefill_ticks": by_kind["prefill"], "decode_ticks": by_kind["decode"],
                "slot_ticks_busy": sum(t[2] for t in working),
                "slot_ticks": len(working) * sched.slots,
                "kv_positions_live": sum(t[3] for t in working),
                "kv_positions_reserved": (len(working) * sched.slots
                                          * int(cell.config["serve"]["max_out_tokens"]))}
    return {"correct": agree["ok"] and not in_window and not refused,
            "attempted": len(touched), "failed": failed, "memory_peak_bytes": server_peak,
            "end_to_end": end_to_end, "spans": spans, "counters": counters}
