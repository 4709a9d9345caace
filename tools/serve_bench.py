"""graft-serve bench: latency under load, not offline throughput.

Replays one Poisson arrival trace at a target QPS through (a) the
continuous in-flight batching scheduler (``inference/serving``) and (b)
the pre-PR-14 static batcher (accumulate a fixed batch, run
``engine.generate``), reporting per-mode p50/p99 time-to-first-token,
p50/p99 per-token latency, and goodput (completed tokens per second of
wall clock at the offered load). Both modes see the SAME trace, so the
comparison row is apples-to-apples: the acceptance claim is that
continuous batching beats static batching on goodput at equal offered
load (PERF.md §PR14).

Run: python tools/serve_bench.py    (background it; poll stdout)
Env: SERVE_MODEL=test|125m|350m...   model family config
     SERVE_MODE=continuous,static   comma list; "both" = the comparison
     SERVE_QPS=4.0                  offered load (Poisson arrivals)
     SERVE_REQUESTS=32              trace length
     SERVE_PROMPT=64 SERVE_NEW=32   tokens per request
     SERVE_NEW_JITTER=0             1 = ragged output budgets: max_new ~
                                    U[NEW/4, NEW] per request (real traces
                                    finish at different lengths — a static
                                    batch decodes to its max while
                                    continuous retires slots early)
     SERVE_LONG_EVERY=0             every Nth request gets a 4x prompt
                                    (continuous-only modes; exercises
                                    chunked prefill under decode load)
     SERVE_SLOTS=8                  decode slots (= static batch size)
     SERVE_CHUNK=16                 prefill chunk (0 = prompt-sized, i.e.
                                    chunked prefill OFF)
     SERVE_SPEC=0 SERVE_SPEC_K=4    speculative decoding (KD student
                                    drafter, half the target's layers)
     SERVE_POOL_TOKENS=0            KV pool budget (0 = slots x context)
     SERVE_POOL_BYTES=0             KV pool BYTE budget (wins over tokens;
                                    the quant A/B's shared-HBM constraint)
     SERVE_WQ=fp                    served weight dtype (fp|int8|int4) for
                                    continuous rows; quant_ab's quant arm
                                    uses int8 unless int4 is set here
     SERVE_KV_QUANT=1               int8 KV pools for continuous rows
                                    (the graft-quant-serve serving default)
  SERVE_MODE may also name "quant_ab": the graft-quant-serve comparison —
  the SAME trace served twice, fp weights + fp KV vs int8 weights + int8
  KV, under the SAME KV byte budget (SERVE_POOL_BYTES), reporting
  blocks-per-GB, goodput ratio at the offered load, and the token-level
  greedy match rate of the quantized arm against fp (PERF.md §PR16).
  SERVE_MODE may also name "prefix_ab" (or pass --prefix-ab): the
  graft-prefix-cache comparison — the SAME trace (use
  SERVE_SHARED_PREFIX for a trace that actually shares prefixes) served
  twice, prefix cache ON vs OFF, at IDENTICAL pool bytes, reporting
  goodput ratio, TTFT p99 per arm, hit rate / cached blocks, and the
  token-level greedy match of the cached arm against the uncached one —
  which must be EXACT: restored KV rows are the same bytes prefill
  would have written (PERF.md §PR19).
     SERVE_SHARED_PREFIX=0           >0 = shared-prefix workload family:
                                    that many template prefixes (each
                                    3/4 of SERVE_PROMPT tokens); request
                                    i takes template i%N + a unique
                                    suffix. Deterministic from
                                    SERVE_SEED, so every arm replays the
                                    identical trace
  SERVE_MODE may also name "fleet" (or pass --fleet): the graft-fleet
  scaling row — the SAME trace replayed through a FleetRouter over
  SERVE_REPLICAS subprocess workers (fleet/worker.py, compile off the
  clock), reporting aggregate goodput + TTFT p99 so 1/2/4-replica runs
  show the scaling claim (PERF.md §PR17). Fleet is subprocess-only and
  must be the sole mode in the run.
     SERVE_REPLICAS=2               fleet mode: worker process count
     SERVE_TICK_MS=0                fleet mode: emulated per-tick device
                                    time per replica (FLEET_TICK_SLEEP_MS)
                                    — a 1-core CPU rig cannot overlap N
                                    replicas' compute, so the scaling row
                                    runs in the device-bound regime a
                                    real per-replica accelerator gives
     SERVE_TELEMETRY=0              per-tick spans + serve events to a
                                    graft-trace JSONL run dir (drift
                                    summary rides the continuous row)
     SERVE_TELEMETRY_DIR=/tmp/ds_tpu_serve_telemetry
     SERVE_SEED=0
One process holds the chip; fleet mode starts one worker process per
replica, which needs one chip each (ROADMAP launcher audit).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # bench_core

import numpy as np

MODEL = os.environ.get("SERVE_MODEL", "350m")
MODES = os.environ.get("SERVE_MODE", "both")
QPS = float(os.environ.get("SERVE_QPS", "4.0"))
REQUESTS = int(os.environ.get("SERVE_REQUESTS", "32"))
PROMPT = int(os.environ.get("SERVE_PROMPT", "64"))
NEW = int(os.environ.get("SERVE_NEW", "32"))
LONG_EVERY = int(os.environ.get("SERVE_LONG_EVERY", "0"))
NEW_JITTER = os.environ.get("SERVE_NEW_JITTER", "0") == "1"
SLOTS = int(os.environ.get("SERVE_SLOTS", "8"))
CHUNK = int(os.environ.get("SERVE_CHUNK", "16"))
SPEC = os.environ.get("SERVE_SPEC", "0") == "1"
SPEC_K = int(os.environ.get("SERVE_SPEC_K", "4"))
POOL_TOKENS = int(os.environ.get("SERVE_POOL_TOKENS", "0"))
POOL_BYTES = int(os.environ.get("SERVE_POOL_BYTES", "0"))
WQ = os.environ.get("SERVE_WQ", "fp")
KV_QUANT = os.environ.get("SERVE_KV_QUANT", "1") == "1"
TELEMETRY = os.environ.get("SERVE_TELEMETRY", "0") == "1"
SEED = int(os.environ.get("SERVE_SEED", "0"))
SHARED_PREFIX = int(os.environ.get("SERVE_SHARED_PREFIX", "0"))
REPLICAS = int(os.environ.get("SERVE_REPLICAS", "2"))
TICK_MS = float(os.environ.get("SERVE_TICK_MS", "0"))


def build_engine(n_positions):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config(MODEL, n_positions=n_positions, dtype=None)
    model = GPT2LMHeadModel(cfg)
    engine = deepspeed_tpu.init_inference(model, replace_with_kernel_inject=True,
                                          max_out_tokens=n_positions)
    return engine, cfg


def build_drafter(engine, cfg, n_positions):
    """The speculation drafter: a layer-reduced KD student seeded from the
    target's own layers (``compression.compress.student_initialization``)
    — the in-tree half the ISSUE names; a trained student drops in the
    same way."""
    import jax
    import flax.linen as nn
    from deepspeed_tpu.compression.compress import student_initialization
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    n_student = max(1, cfg.n_layer // 2)
    # evenly spaced teacher layers seed the student (standard KD recipe)
    teacher_layers = [int(round(i * (cfg.n_layer - 1) / max(n_student - 1, 1)))
                      for i in range(n_student)]
    dcfg = get_gpt2_config(MODEL, n_positions=n_positions, dtype=None,
                           n_layer=n_student)
    drafter = GPT2LMHeadModel(dcfg)
    d_init = nn.meta.unbox(drafter.init(jax.random.PRNGKey(1),
                                        np.zeros((1, 8), np.int32))["params"])
    d_params = student_initialization(
        d_init, jax.device_get(nn.meta.unbox(engine.params)),
        {"compression_training": {"layer_reduction": {
            "enabled": True, "module_name_prefix": "h",
            "teacher_layer": teacher_layers,
            "other_module_name": ["wte", "wpe", "ln_f"]}}})
    return drafter, d_params, teacher_layers


def poisson_trace(rng, vocab):
    """[(arrival_offset_s, prompt, max_new)] — one trace shared by every
    mode so offered load is identical across the comparison."""
    gaps = rng.exponential(1.0 / QPS, REQUESTS)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(REQUESTS):
        p = PROMPT * 4 if LONG_EVERY and (i + 1) % LONG_EVERY == 0 else PROMPT
        prompt = rng.integers(0, vocab, (p,)).astype(np.int32)
        n = int(rng.integers(max(NEW // 4, 1), NEW + 1)) if NEW_JITTER else NEW
        trace.append((float(arrivals[i]), prompt, n))
    return trace


def shared_prefix_trace(rng, vocab):
    """The graft-prefix-cache workload family: ``SERVE_SHARED_PREFIX``
    template prefixes (each 3/4 of SERVE_PROMPT tokens, drawn once up
    front), each request = a uniformly drawn template + a unique random
    suffix, arrivals Poisson at SERVE_QPS. Everything is drawn from the
    seeded ``rng``, so cache-on and cache-off arms replay the IDENTICAL
    trace — the A/B's whole premise. Template choice is random rather
    than round-robin: a cyclic assignment resonates with alternating
    least-loaded dispatch (period N divisible by the replica count
    partitions templates perfectly by accident), which would make the
    affinity-vs-least-loaded control meaningless."""
    gaps = rng.exponential(1.0 / QPS, REQUESTS)
    arrivals = np.cumsum(gaps)
    shared = max((PROMPT * 3) // 4, 1)
    templates = [rng.integers(0, vocab, (shared,)).astype(np.int32)
                 for _ in range(SHARED_PREFIX)]
    trace = []
    for i in range(REQUESTS):
        suffix = rng.integers(0, vocab, (PROMPT - shared,)).astype(np.int32)
        t = int(rng.integers(0, SHARED_PREFIX))
        prompt = np.concatenate([templates[t], suffix])
        n = int(rng.integers(max(NEW // 4, 1), NEW + 1)) if NEW_JITTER else NEW
        trace.append((float(arrivals[i]), prompt, n))
    return trace


def _lat_row(hist):
    if hist is None or (hasattr(hist, "count") and not hist.count):
        return None
    snap = hist.snapshot() if hasattr(hist, "snapshot") else hist
    return {k: round(v, 4) for k, v in snap.items()
            if k in ("p50", "p90", "p99", "min", "max", "mean")}


def serve_evidence(engine, slots, wq="fp", kv_quant=False):
    """Static lint + cost evidence for the decode program this run serves
    (the perf-ladder contract: a banked latency row must prove its
    program passes the same gates CI enforces). ``wq``/``kv_quant`` price
    the QUANTIZED program when a quantized row banks evidence."""
    try:
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu import analysis
        from deepspeed_tpu.analysis.memory import estimate_memory
        from deepspeed_tpu.analysis.program import ProgramInfo
        from deepspeed_tpu.inference.serving import make_slot_cache
        from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                              make_apply_fn)

        slots = engine._pow2_bucket(slots)  # price the program actually served
        module, params = engine.module, engine.params
        if wq != "fp":
            from deepspeed_tpu.inference.serving.scheduler import _quant_view
            module, params = _quant_view(module, params, wq, 64)
        cache = make_slot_cache(module, slots, kv_quant=kv_quant)
        decode = build_decode_step(make_apply_fn(module, engine._mparams),
                                   False, 1.0, 0, 1.0)
        write_pos = jnp.zeros((slots,), jnp.int32)
        jaxpr = jax.make_jaxpr(decode)(params, cache, write_pos)
        info = ProgramInfo(name="serve_decode", jaxpr=jaxpr, kind="serve_decode")
        findings, _ = analysis.run_program_rules(info)
        mem = estimate_memory(info)
        return {"serve_lint": analysis.summarize(findings),
                "serve_cost_peak_bytes": mem.peak_bytes,
                "serve_cost_transient_bytes": mem.peak_transient_bytes,
                "serve_weight_dtype": wq, "serve_kv_quant": kv_quant}
    except Exception as e:  # evidence must never kill a run
        return {"serve_evidence_error": f"{type(e).__name__}: {str(e)[:120]}"}


def run_continuous(engine, cfg, trace, drafter=None, telemetry=None,
                   wq=None, kv_quant=None, pool_bytes=None, label="continuous",
                   collect_outputs=False, prefix_cache="on"):
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 Request, ServingConfig)

    n_positions = cfg.n_positions
    scfg = ServingConfig(
        slots=SLOTS, page_size=16,
        kv_pool_tokens=POOL_TOKENS or None,
        kv_pool_bytes=(POOL_BYTES or None) if pool_bytes is None else pool_bytes,
        # the quant_ab arms pass wq; every other run serves SERVE_WQ
        weight_dtype=WQ if wq is None else wq,
        kv_quant=KV_QUANT if kv_quant is None else kv_quant,
        # the prefix_ab arms pass "on"/"off"
        prefix_cache=prefix_cache,
        prefill_chunk=CHUNK if CHUNK > 0 else n_positions,
        speculation={"enabled": drafter is not None, "k": SPEC_K})
    sched = ContinuousBatchingScheduler(engine, scfg, drafter=drafter,
                                        telemetry=telemetry)
    # compile every serving program off the clock — including rare-path
    # ones a warm request can't reliably reach, like the drafter's
    # full-k refeed verify (latency-under-load must not charge a
    # mid-serve request for XLA compile time)
    sched.warmup()

    t0 = time.monotonic()
    i = 0
    reqs = []
    while i < len(trace) or sched.busy:
        now = time.monotonic() - t0
        while i < len(trace) and trace[i][0] <= now:
            _, prompt, new = trace[i]
            r = Request(prompt=prompt, max_new_tokens=new,
                        arrival_time=t0 + trace[i][0])
            sched.submit(r)
            reqs.append(r)
            i += 1
        if sched.busy:
            sched.step()
        elif i < len(trace):
            time.sleep(min(max(trace[i][0] - now, 0.0), 0.05))
    wall = time.monotonic() - t0
    stats = sched.stats()
    row = {
        "mode": label, "wall_s": round(wall, 3),
        "finished": stats["finished"], "refused": stats["refused"],
        "goodput_tok_s": round(stats["generated_tokens"] / wall, 1),
        "ttft": _lat_row(stats["ttft"]), "per_token": _lat_row(stats["per_token"]),
        "ticks": stats["ticks"], "pool": stats["pool"],
        "weight_dtype": stats["weight_dtype"],
        "kv_quant": stats["kv_quant"],
        "prefix_cache": stats["prefix_cache"],
        "cached_prefix_tokens": stats["cached_prefix_tokens"],
        "prefix_hit_rate": stats["pool"].get("prefix_hit_rate"),
        "chunked_prefill": CHUNK > 0, "prefill_chunk": CHUNK or n_positions,
        "slots": sched.slots,
    }
    if collect_outputs:
        row["_outputs"] = [list(r.output) for r in reqs]
    if drafter is not None:
        row["speculation"] = {"k": SPEC_K,
                              "drafted": stats["drafted"],
                              "accepted": stats["accepted"],
                              "acceptance_rate": round(stats["acceptance_rate"], 3)
                              if stats["acceptance_rate"] is not None else None}
    if telemetry is not None and telemetry.enabled:
        row["telemetry"] = telemetry.drift_summary()
    return row


def _token_match(quant_outputs, fp_outputs):
    """Token-level greedy match of the quantized arm against fp — the
    speculative-acceptance metric applied across serving stacks: per
    request, the longest common prefix counts as accepted (a diverged
    token invalidates its suffix exactly as a rejected draft would)."""
    accepted = total = 0
    exact = 0
    for q, f in zip(quant_outputs, fp_outputs):
        n = 0
        for a, b in zip(q, f):
            if a != b:
                break
            n += 1
        accepted += n
        total += max(len(f), len(q))
        exact += int(q == f and len(q) > 0)
    return {"token_match_rate": round(accepted / max(total, 1), 4),
            "exact_output_requests": exact, "requests": len(fp_outputs)}


def quant_ab(engine, cfg, trace, header, drafter=None):
    """The graft-quant-serve A/B (PERF.md §PR16): the same trace served by
    the fp stack and by the int8-weight + int8-KV stack under the SAME KV
    byte budget (SERVE_POOL_BYTES; defaults to the fp pool's full-context
    footprint HALVED, so the budget is genuinely scarce for fp). Reports
    blocks-per-GB, goodput ratio, and the token-level greedy match."""
    budget = POOL_BYTES
    if not budget:
        fp_probe = _probe_kv_bytes_per_token(engine, cfg)
        budget = int(SLOTS * cfg.n_positions * fp_probe) // 2
        print(f"# quant_ab: SERVE_POOL_BYTES unset, using half the fp "
              f"full-context footprint = {budget} bytes", flush=True)
    wq = WQ if WQ != "fp" else "int8"
    arms = {}
    for label, arm_wq, kvq in (("fp", "fp", False), ("quant", wq, True)):
        row = run_continuous(engine, cfg, trace, drafter=drafter, wq=arm_wq,
                             kv_quant=kvq, pool_bytes=budget,
                             label=f"quant_ab:{label}", collect_outputs=True)
        row.update(serve_evidence(engine, SLOTS, wq=arm_wq, kv_quant=kvq))
        arms[label] = row
        printable = dict(header, **{k: v for k, v in row.items()
                                    if not k.startswith("_")})
        print(json.dumps(printable), flush=True)
    fp_row, q_row = arms["fp"], arms["quant"]
    comparison = {
        "comparison": "quant_vs_fp", "qps": QPS, "weight_dtype": wq,
        "kv_pool_bytes": budget,
        "kv_blocks_fp": fp_row["pool"]["num_blocks"],
        "kv_blocks_quant": q_row["pool"]["num_blocks"],
        "kv_blocks_per_gb_fp": fp_row["pool"]["kv_blocks_per_gb"],
        "kv_blocks_per_gb_quant": q_row["pool"]["kv_blocks_per_gb"],
        "goodput_fp_tok_s": fp_row["goodput_tok_s"],
        "goodput_quant_tok_s": q_row["goodput_tok_s"],
        "goodput_ratio": round(q_row["goodput_tok_s"]
                               / max(fp_row["goodput_tok_s"], 1e-9), 3),
        "greedy_match": _token_match(q_row["_outputs"], fp_row["_outputs"]),
        "quant_beats_fp_goodput":
            q_row["goodput_tok_s"] > fp_row["goodput_tok_s"],
        "quant_more_blocks_per_gb":
            q_row["pool"]["kv_blocks_per_gb"] > fp_row["pool"]["kv_blocks_per_gb"],
    }
    print(json.dumps(comparison), flush=True)
    return comparison


def prefix_ab(engine, cfg, trace, header, drafter=None):
    """The graft-prefix-cache A/B (PERF.md §PR19): the same trace served
    twice — prefix cache OFF then ON — with IDENTICAL pool sizing (same
    SERVE_POOL_TOKENS/SERVE_POOL_BYTES, asserted on the pool the
    scheduler actually built). Reports goodput ratio, per-arm TTFT p99,
    hit rate / cached-tokens / cached-blocks evidence, and the
    token-level greedy match of the cached arm against the uncached one.
    The match must be EXACT: a cache hit restores the same KV bytes
    prefill would have written, so any divergence is a correctness bug,
    not a tolerance."""
    arms = {}
    for label in ("off", "on"):
        row = run_continuous(engine, cfg, trace, drafter=drafter,
                             prefix_cache=label, label=f"prefix_ab:{label}",
                             collect_outputs=True)
        row.update(serve_evidence(engine, SLOTS, wq=row["weight_dtype"],
                                  kv_quant=row["kv_quant"]))
        arms[label] = row
        printable = dict(header, **{k: v for k, v in row.items()
                                    if not k.startswith("_")})
        print(json.dumps(printable), flush=True)
    off_row, on_row = arms["off"], arms["on"]
    comparison = {
        "comparison": "prefix_cache_on_vs_off", "qps": QPS,
        "shared_prefix_templates": SHARED_PREFIX or None,
        "pool_blocks_off": off_row["pool"]["num_blocks"],
        "pool_blocks_on": on_row["pool"]["num_blocks"],
        "pool_blocks_equal":
            off_row["pool"]["num_blocks"] == on_row["pool"]["num_blocks"],
        "prefix_hit_rate": on_row["prefix_hit_rate"],
        "cached_prefix_tokens": on_row["cached_prefix_tokens"],
        "cached_blocks_final": on_row["pool"]["cached_blocks"],
        "published_blocks": on_row["pool"]["published_blocks"],
        "goodput_off_tok_s": off_row["goodput_tok_s"],
        "goodput_on_tok_s": on_row["goodput_tok_s"],
        "goodput_ratio": round(on_row["goodput_tok_s"]
                               / max(off_row["goodput_tok_s"], 1e-9), 3),
        "ttft_p99_off": (off_row["ttft"] or {}).get("p99"),
        "ttft_p99_on": (on_row["ttft"] or {}).get("p99"),
        "ttft_p99_improved":
            (on_row["ttft"] or {}).get("p99") is not None
            and (off_row["ttft"] or {}).get("p99") is not None
            and on_row["ttft"]["p99"] < off_row["ttft"]["p99"],
        "greedy_match": _token_match(on_row["_outputs"], off_row["_outputs"]),
        "cache_on_beats_off_goodput":
            on_row["goodput_tok_s"] > off_row["goodput_tok_s"],
    }
    print(json.dumps(comparison), flush=True)
    return comparison


def _probe_kv_bytes_per_token(engine, cfg):
    """The fp cache's per-token KV footprint, measured the same way the
    scheduler's byte-budget sizing measures it."""
    from deepspeed_tpu.inference.serving import ServingConfig
    from deepspeed_tpu.inference.serving.scheduler import ContinuousBatchingScheduler
    probe = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=SLOTS, kv_quant=False))
    return probe._kv_bytes_per_token()


def run_static(engine, cfg, trace):
    """The pre-PR-14 baseline: accumulate arrivals into fixed batches of
    ``SLOTS`` and run offline ``engine.generate`` per batch. Every token
    of a request becomes available only when its whole batch finishes —
    which is exactly the latency story continuous batching replaces."""
    from deepspeed_tpu.runtime.telemetry import Histogram

    # warm the generate programs off the clock at the REAL batch bucket
    # (generate caches per pow2 bucket: a batch-1 warm would leave the
    # timed flushes paying the SLOTS-bucket compile — same courtesy as
    # continuous warming its own fixed-shape programs)
    engine.generate(np.repeat(trace[0][1][None, :], SLOTS, axis=0),
                    max_new_tokens=2)

    ttft_h, tok_h = Histogram(), Histogram()
    t0 = time.monotonic()
    i, batch, finished, tokens_out = 0, [], 0, 0
    while i < len(trace) or batch:
        now = time.monotonic() - t0
        while i < len(trace) and trace[i][0] <= now:
            batch.append(trace[i])
            i += 1
        flush = len(batch) >= SLOTS or (batch and i >= len(trace))
        if flush:
            part, batch = batch[:SLOTS], batch[SLOTS:]
            prompts = np.stack([p for _, p, _ in part])
            new = max(n for _, _, n in part)
            out = np.asarray(engine.generate(prompts, max_new_tokens=new))
            done = time.monotonic() - t0
            per_tok = (done - now) / max(new, 1)
            for arr, _, n in part:
                ttft_h.record(done - arr)   # first token only at batch end
                for _ in range(n - 1):
                    tok_h.record(per_tok)
                finished += 1
                tokens_out += n
            del out
        elif i < len(trace):
            time.sleep(min(max(trace[i][0] - now, 0.0), 0.05))
    wall = time.monotonic() - t0
    return {"mode": "static", "wall_s": round(wall, 3), "finished": finished,
            "refused": 0, "goodput_tok_s": round(tokens_out / wall, 1),
            "ttft": _lat_row(ttft_h), "per_token": _lat_row(tok_h),
            "batch": SLOTS}


def run_fleet(cfg, trace, n_positions):
    """The graft-fleet scaling row: replay the shared Poisson trace
    through a FleetRouter over ``REPLICAS`` real worker subprocesses.
    Engine build + warmup happen in each worker BEFORE the clock starts
    (``wait_ready``), so the timed window measures serving, not XLA.
    TTFT is the per-request value each worker's scheduler measured
    (dispatch is immediate, so worker admission ≈ router arrival)."""
    import shutil
    import tempfile

    from deepspeed_tpu.inference.fleet import FleetRouter, SubprocessReplica
    from deepspeed_tpu.runtime.telemetry import Histogram

    workdir = tempfile.mkdtemp(prefix="ds_tpu_fleet_")
    env = {"FLEET_MODEL": MODEL, "FLEET_POSITIONS": str(n_positions),
           "FLEET_SLOTS": str(SLOTS),
           "FLEET_CHUNK": str(CHUNK if CHUNK > 0 else n_positions),
           "FLEET_KV_QUANT": "1" if KV_QUANT else "0"}
    if POOL_TOKENS:
        env["FLEET_POOL_TOKENS"] = str(POOL_TOKENS)
    if TICK_MS:
        env["FLEET_TICK_SLEEP_MS"] = str(TICK_MS)
    if TELEMETRY:
        env["FLEET_TELEMETRY_DIR"] = os.environ.get(
            "SERVE_TELEMETRY_DIR", "/tmp/ds_tpu_serve_telemetry")
    # prefix-affinity dispatch A/B toggle (FLEET_AFFINITY=0 = pure
    # least-loaded): the serve_prefix_fleet_* perf-ladder rungs compare
    # the two on the same shared-prefix trace
    affinity = os.environ.get("FLEET_AFFINITY", "1") == "1"
    router = FleetRouter(heartbeat_timeout=120.0, affinity=affinity)
    replicas = [SubprocessReplica(f"w{i}", os.path.join(workdir, f"w{i}"),
                                  env=env)
                for i in range(REPLICAS)]
    try:
        for r in replicas:
            r.wait_ready(timeout=600.0)
            router.add_replica(r.name, r)
        print(f"# fleet: {REPLICAS} replica(s) ready, replaying trace",
              flush=True)
        t0 = time.monotonic()
        i = 0
        while i < len(trace) or router.pending:
            now = time.monotonic() - t0
            while i < len(trace) and trace[i][0] <= now:
                _, prompt, new = trace[i]
                router.submit(prompt, new)
                i += 1
            router.poll()
            if not router.pending and i < len(trace):
                time.sleep(min(max(trace[i][0] - now, 0.0), 0.05))
            else:
                time.sleep(0.005)
        wall = time.monotonic() - t0
        ttft_h = Histogram()
        tokens_out = 0
        for rec in router.completed.values():
            st = rec.get("stats") or {}
            if st.get("ttft") is not None:
                ttft_h.record(st["ttft"])
            tokens_out += st.get("new_tokens") or len(rec.get("output") or [])
        rstats = router.stats()
        return {
            "mode": f"fleet:{REPLICAS}", "replicas": REPLICAS,
            "wall_s": round(wall, 3),
            "finished": rstats["completed"], "failed": rstats["failed"],
            "duplicate_completions": rstats["duplicate_completions"],
            "readmitted": rstats["readmitted"],
            "completed_by": rstats["completed_by"],
            "affinity": rstats["affinity"],
            "affinity_hits": rstats["affinity_hits"],
            "affinity_overruled": rstats["affinity_overruled"],
            "ticks_by": {r.name: r.ticks_seen for r in replicas},
            "goodput_tok_s": round(tokens_out / wall, 1),
            "ttft": _lat_row(ttft_h),
            "slots_per_replica": SLOTS, "kv_quant": KV_QUANT,
            "chunked_prefill": CHUNK > 0,
            "prefill_chunk": CHUNK or n_positions,
            "emulated_tick_ms": TICK_MS or None,
        }
    finally:
        for r in replicas:
            r.close()
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    import jax

    from bench_core import enable_compile_cache

    # knob incompatibilities are knowable from env alone — fail them
    # BEFORE paying minutes of engine build + compile + continuous replay
    modes = ["continuous", "static"] if MODES == "both" else MODES.split(",")
    if "--fleet" in sys.argv:
        modes = ["fleet"]
    if "--prefix-ab" in sys.argv:
        modes = ["prefix_ab"]
    unknown = [m for m in modes
               if m not in ("continuous", "static", "quant_ab", "prefix_ab",
                            "fleet")]
    if unknown:
        raise SystemExit(f"unknown SERVE_MODE entry {unknown[0]!r}")
    if "fleet" in modes and modes != ["fleet"]:
        raise SystemExit("fleet mode runs alone (workers own the engines; "
                         "there is no parent engine to share with other modes)")
    if REPLICAS < 1:
        raise SystemExit(f"SERVE_REPLICAS must be >= 1, got {REPLICAS}")
    if WQ not in ("fp", "int8", "int4"):
        raise SystemExit(f"SERVE_WQ must be fp|int8|int4, got {WQ!r}")
    if LONG_EVERY and "static" in modes:
        raise SystemExit(
            "static mode cannot batch ragged prompts (SERVE_LONG_EVERY): "
            "the chunked-prefill A/B is continuous-only — use "
            "SERVE_MODE=continuous")
    if SPEC and "static" in modes:
        print("# static mode ignores SERVE_SPEC (no speculation offline)",
              flush=True)

    enable_compile_cache()
    n_positions = max((PROMPT * 4 if LONG_EVERY else PROMPT) + NEW + 1, 128)
    if modes == ["fleet"]:
        # workers build their own engines; the parent only needs the
        # vocab size to synthesize the trace
        from deepspeed_tpu.models import get_gpt2_config
        engine, cfg = None, get_gpt2_config(MODEL, n_positions=n_positions,
                                            dtype=None)
    else:
        engine, cfg = build_engine(n_positions)
    rng = np.random.default_rng(SEED)
    trace = (shared_prefix_trace(rng, cfg.vocab_size) if SHARED_PREFIX
             else poisson_trace(rng, cfg.vocab_size))

    drafter = None
    if SPEC and ("continuous" in modes or "quant_ab" in modes):
        d_module, d_params, teacher_layers = build_drafter(engine, cfg, n_positions)
        drafter = (d_module, d_params)
        print(f"# drafter: {d_module.config.n_layer}-layer KD student seeded "
              f"from teacher layers {teacher_layers}", flush=True)

    telemetry = None
    if TELEMETRY:
        from deepspeed_tpu.runtime.config import TelemetryConfig
        from deepspeed_tpu.runtime.telemetry import RuntimeTelemetry
        telemetry = RuntimeTelemetry(TelemetryConfig(
            enabled=True,
            output_path=os.environ.get("SERVE_TELEMETRY_DIR",
                                       "/tmp/ds_tpu_serve_telemetry"),
            job_name=f"serve_{MODEL}_qps{QPS}"))
        # graft-calibrate separation markers (same contract as the fleet
        # worker's header): the field's presence keys collect_samples'
        # mixed-run refusal for serve-scope samples
        telemetry.write_run_header({"bench": "serve_bench", "model": MODEL,
                                    "qps": QPS, "slots": SLOTS,
                                    "prefix_cache": "on",
                                    "cached_prefix_tokens": 0})

    rows = {}
    header = {"model": MODEL, "qps": QPS, "requests": REQUESTS, "prompt": PROMPT,
              "new": NEW, "new_jitter": NEW_JITTER, "long_every": LONG_EVERY,
              "slots": SLOTS, "backend": jax.default_backend(), "seed": SEED,
              "shared_prefix": SHARED_PREFIX or None}
    for mode in modes:
        if mode == "continuous":
            row = run_continuous(engine, cfg, trace, drafter=drafter,
                                 telemetry=telemetry)
            row.update(serve_evidence(engine, SLOTS,
                                      wq=row["weight_dtype"],
                                      kv_quant=row["kv_quant"]))
        elif mode == "quant_ab":
            quant_ab(engine, cfg, trace, header, drafter=drafter)
            continue
        elif mode == "prefix_ab":
            prefix_ab(engine, cfg, trace, header, drafter=drafter)
            continue
        elif mode == "fleet":
            row = run_fleet(cfg, trace, n_positions)
        else:
            row = run_static(engine, cfg, trace)
        rows[mode] = dict(header, **row)
        print(json.dumps(rows[mode]), flush=True)
    if telemetry is not None:
        telemetry.close()
    if "continuous" in rows and "static" in rows:
        c, s = rows["continuous"], rows["static"]
        comparison = {
            "comparison": "continuous_vs_static", "qps": QPS,
            "goodput_ratio": round(c["goodput_tok_s"] / max(s["goodput_tok_s"], 1e-9), 3),
            "ttft_p99_ratio": (round(c["ttft"]["p99"] / s["ttft"]["p99"], 3)
                               if c.get("ttft") and s.get("ttft") else None),
            "continuous_beats_static_goodput":
                c["goodput_tok_s"] > s["goodput_tok_s"],
        }
        print(json.dumps(comparison), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
