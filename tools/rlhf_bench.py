"""RLHF hybrid-engine throughput bench — graft-rlhf A/B edition.

The reference's DeepSpeed-Chat claims (``blogs/deepspeed-chat/README.md:30``
"15x faster") price exactly the phases measured here, but its hybrid
engine runs them as *serial offline phases*: generate() blocks the
learner, and every rollout in a static batch decodes to the longest
budget in its cohort. PR 20 rebuilds the generation phase on the
continuous scheduler, so this bench is now an A/B on the SAME prompt
trace (deterministic indexed prompts + per-rollout token budgets):

- ``off`` — the serial baseline: generate-then-train per learner batch,
  static batching (the whole cohort decodes to its max budget, outputs
  trimmed to per-rollout budgets so both arms bank identical experience).
- ``on``  — the in-flight loop (``runtime/rlhf``): prompts stream into a
  ContinuousBatchingScheduler, finished slots re-admit immediately, the
  learner interleaves at decode-tick granularity, weight sync is
  planner-priced + digest-verified per ``RLHF_SYNC_EVERY`` learner steps.

Goodput = banked experience tokens / wall seconds at EQUAL experience
count (same budgets, same learner-step count). ``ab`` mode runs both and
emits a ratio row — the ``>= 1.3x`` acceptance evidence.

Telemetry (RLHF_TELEMETRY=dir): the on-arm stamps two run headers in
separate sinks — scope ``rlhf_rollout`` with the scheduler's
``serving_static_price()`` (the graft-calibrate fit source) and scope
``rlhf_learner`` with the train step's static price; both carry the
``rlhf_overlap`` separation marker ``collect_samples`` keys its
mixed-run refusal on.

Run: python tools/rlhf_bench.py     (one process holds the chip)
Env: RLHF_MODE=ab|on|off RLHF_MODEL=test RLHF_BATCH=8 RLHF_PROMPT=16
     RLHF_NEW=32 RLHF_ROLLOUTS=32 RLHF_SLOTS=8 RLHF_SYNC_EVERY=1
     RLHF_ZERO=3 RLHF_TICK_SLEEP_MS=0 RLHF_TELEMETRY=
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

MODE = os.environ.get("RLHF_MODE", "ab")
MODEL = os.environ.get("RLHF_MODEL", "test")
BATCH = int(os.environ.get("RLHF_BATCH", "8"))          # learner batch
PROMPT = int(os.environ.get("RLHF_PROMPT", "16"))
NEW = int(os.environ.get("RLHF_NEW", "32"))             # max token budget
ROLLOUTS = int(os.environ.get("RLHF_ROLLOUTS", "32"))
SLOTS = int(os.environ.get("RLHF_SLOTS", str(BATCH)))
SYNC_EVERY = int(os.environ.get("RLHF_SYNC_EVERY", "1"))
ZERO = int(os.environ.get("RLHF_ZERO", "3"))
TICK_SLEEP_MS = float(os.environ.get("RLHF_TICK_SLEEP_MS", "0"))
TELEMETRY = os.environ.get("RLHF_TELEMETRY", "")


def budget(i: int) -> int:
    """Deterministic per-rollout token budget in [max(4, NEW//4), NEW] —
    the long-tail mix that makes static cohorts pay max-budget decode for
    every member while the continuous scheduler re-admits freed slots."""
    lo = max(4, NEW // 4)
    return lo + (i * 7919) % (NEW - lo + 1)


def prompt_tokens(i: int, vocab: int) -> np.ndarray:
    r = np.random.RandomState(1234 + i)
    return r.randint(0, vocab, size=(PROMPT,)).astype(np.int32)


def build_engine(jnp):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    import jax
    cfg = get_gpt2_config(MODEL, n_positions=PROMPT + NEW, dtype=None)
    model = GPT2LMHeadModel(cfg)

    def loss_fn(logits, batch):
        tok = batch["rollouts"]
        adv = batch["advantage"]
        mask = batch["mask"].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logp, tok[:, 1:, None], axis=-1)[..., 0]
        return -(adv[:, None] * tgt * mask[:, 1:]).sum() \
            / jnp.maximum(mask[:, 1:].sum(), 1.0)

    ds = {"train_batch_size": BATCH,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-5}},
          "gradient_clipping": 1.0,
          "zero_optimization": {"stage": ZERO,
                                **({"stage3_param_persistence_threshold": 0}
                                   if ZERO == 3 else {})},
          "hybrid_engine": {"enabled": True, "max_out_tokens": PROMPT + NEW,
                            "inference_tp_size": 1},
          "steps_per_print": 10**9}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds,
                                               loss_fn=loss_fn)
    example = _pad_batch([(np.zeros(PROMPT, np.int32), np.zeros(0, np.int32))]
                         * BATCH, np.zeros(BATCH, np.float32))
    engine.initialize_state(example)
    return engine, cfg


def _pad_batch(pairs, adv):
    """(prompt, output) pairs -> fixed-width learner batch with a loss
    mask over the generated positions (identical shape both arms)."""
    width = PROMPT + NEW
    toks = np.zeros((len(pairs), width), np.int32)
    mask = np.zeros((len(pairs), width), np.float32)
    for j, (p, o) in enumerate(pairs):
        seq = np.concatenate([np.asarray(p, np.int32),
                              np.asarray(o, np.int32)])[:width]
        toks[j, :len(seq)] = seq
        mask[j, len(p):len(seq)] = 1.0
    return {"input_ids": toks, "rollouts": toks, "advantage": adv,
            "mask": mask}


def _advantage(pairs):
    reward = np.asarray([(np.asarray(o) % 2 == 0).mean() if len(o) else 0.0
                         for _, o in pairs], np.float32)
    return reward - reward.mean()


def _learner_batch(pairs):
    return _pad_batch(pairs, _advantage(pairs))


def _sync_summary(log):
    if not log:
        return None
    last = log[-1]
    return {"syncs": len(log),
            "generation": last.get("generation"),
            "gather_bytes": last.get("gather_bytes"),
            "total_bytes": last.get("total_bytes"),
            "digest_verified": bool(last.get("digest")),
            "error": last.get("error")}


def _telemetry(job, scope, overlap, static_price):
    if not TELEMETRY:
        return None
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import RuntimeTelemetry
    import jax
    t = RuntimeTelemetry(TelemetryConfig(enabled=True, output_path=TELEMETRY,
                                         job_name=job))
    t.write_run_header(
        {"bench": "rlhf_bench", "model": MODEL, "backend": jax.default_backend(),
         "scope": scope, "rlhf_overlap": overlap,
         "batch": BATCH, "prompt": PROMPT, "new": NEW},
        static_price=static_price)
    return t


def run_off(engine, cfg):
    """Serial baseline: static generate-then-train, cohort-max decode."""
    import jax
    total = ROLLOUTS
    n_batches = total // BATCH

    def cohort(k, timed):
        idxs = list(range(k * BATCH, (k + 1) * BATCH))
        prompts = np.stack([prompt_tokens(i, cfg.vocab_size) for i in idxs])
        maxb = max(budget(i) for i in idxs)
        t0 = time.perf_counter()
        out = np.asarray(engine.generate(prompts, max_new_tokens=maxb))
        gen_s = time.perf_counter() - t0
        pairs = [(prompts[j], out[j, PROMPT:PROMPT + budget(i)])
                 for j, i in enumerate(idxs)]
        t0 = time.perf_counter()
        loss = float(engine.train_batch(_learner_batch(pairs)))
        jax.block_until_ready(engine.state.params)
        train_s = time.perf_counter() - t0
        if TICK_SLEEP_MS and timed:
            # emulated-device regime: the serial arm's generate ticks run
            # on-device too — maxb decode ticks, nothing overlapped
            time.sleep(TICK_SLEEP_MS / 1e3 * maxb)
        return pairs, gen_s, train_s, loss

    cohort(0, timed=False)  # warmup: compiles generate + reshard + train
    t_all = time.perf_counter()
    gen_s = train_s = 0.0
    tokens = 0
    losses = []
    steps = 0
    for k in range(n_batches):
        pairs, g, t, loss = cohort(k, timed=True)
        gen_s += g
        train_s += t
        tokens += sum(len(o) for _, o in pairs)
        losses.append(loss)
        steps += 1
    wall = time.perf_counter() - t_all
    return {"mode": "rlhf_overlap_off", "rollouts": n_batches * BATCH,
            "experience_tokens": tokens, "wall_s": round(wall, 3),
            "goodput_tok_s": round(tokens / wall, 2),
            "gen_s": round(gen_s, 3), "train_s": round(train_s, 3),
            "learner_steps": steps, "loss_last": losses[-1],
            "weight_sync": _sync_summary(engine.weight_sync_log)}


def run_on(engine, cfg):
    """In-flight loop: continuous scheduler + tick-interleaved learner."""
    from deepspeed_tpu.inference.serving import Request, ServingConfig
    from deepspeed_tpu.runtime.rlhf import RolloutConfig, RolloutLoop

    def prompt_fn(i):
        return Request(prompt=prompt_tokens(i, cfg.vocab_size),
                       max_new_tokens=budget(i))

    def make_batch(exps):
        pairs = [(np.asarray(e.prompt, np.int32),
                  np.asarray(e.output, np.int32)) for e in exps]
        return _learner_batch(pairs)

    scfg = ServingConfig(slots=SLOTS, prefill_chunk=PROMPT)
    warm = RolloutLoop(engine, prompt_fn, make_batch,
                       RolloutConfig(train_batch_size=BATCH,
                                     total_rollouts=BATCH, sync_every=1),
                       serving_config=scfg)
    warm.run(max_ticks=10**6)  # warmup: serve programs + train + sync

    telemetry = _telemetry("rlhf_rollout", "rlhf_rollout", "on",
                           warm.scheduler.serving_static_price())
    learner_t = None
    if TELEMETRY:
        from deepspeed_tpu.analysis.cost import static_price_from_programs
        try:
            price = static_price_from_programs(
                engine.traced_programs(
                    _learner_batch([(np.zeros(PROMPT, np.int32),
                                     np.zeros(0, np.int32))] * BATCH),
                    lower=False))
        except Exception as e:
            price = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
        learner_t = _telemetry("rlhf_learner", "rlhf_learner", "on", price)

    loop = RolloutLoop(engine, prompt_fn, make_batch,
                       RolloutConfig(train_batch_size=BATCH,
                                     total_rollouts=ROLLOUTS,
                                     sync_every=SYNC_EVERY,
                                     tick_sleep_ms=TICK_SLEEP_MS),
                       serving_config=scfg, telemetry=telemetry,
                       learner_telemetry=learner_t)
    t0 = time.perf_counter()
    res = loop.run(max_ticks=10**7)
    wall = time.perf_counter() - t0
    for t in (telemetry, learner_t):
        if t is not None:
            t.close()
    stats = res["scheduler_stats"]
    tokens = stats["generated_tokens"]
    return {"mode": "rlhf_overlap_on", "rollouts": res["experience_consumed"],
            "experience_tokens": tokens, "wall_s": round(wall, 3),
            "goodput_tok_s": round(tokens / wall, 2),
            "learner_steps": res["learner_steps"],
            "loss_last": res["losses"][-1]["loss"] if res["losses"] else None,
            "learner_steps_overlapped":
                stats["rollout"]["learner_steps_overlapped"],
            "weight_sync_generation": res["weight_sync_generation"],
            "weight_sync": _sync_summary(res["sync_evidence"]),
            "ticks": stats["ticks"]}


def main():
    import jax
    import jax.numpy as jnp

    from bench_core import enable_compile_cache
    enable_compile_cache()

    assert ROLLOUTS % BATCH == 0, "RLHF_ROLLOUTS must be a multiple of RLHF_BATCH"
    common = {"backend": jax.default_backend(), "model": MODEL,
              "batch": BATCH, "prompt": PROMPT, "new": NEW,
              "rollouts": ROLLOUTS, "slots": SLOTS,
              "sync_every": SYNC_EVERY, "tick_sleep_ms": TICK_SLEEP_MS}
    rows = []
    if MODE in ("off", "ab"):
        engine, cfg = build_engine(jnp)
        rows.append({**common, **run_off(engine, cfg)})
        print(json.dumps(rows[-1]), flush=True)
    if MODE in ("on", "ab"):
        engine, cfg = build_engine(jnp)
        rows.append({**common, **run_on(engine, cfg)})
        print(json.dumps(rows[-1]), flush=True)
    if MODE == "ab":
        off = next(r for r in rows if r["mode"] == "rlhf_overlap_off")
        on = next(r for r in rows if r["mode"] == "rlhf_overlap_on")
        assert on["experience_tokens"] == off["experience_tokens"], \
            (on["experience_tokens"], off["experience_tokens"])
        print(json.dumps({**common, "mode": "rlhf_ab",
                          "experience_tokens": on["experience_tokens"],
                          "goodput_off": off["goodput_tok_s"],
                          "goodput_on": on["goodput_tok_s"],
                          "speedup": round(on["goodput_tok_s"]
                                           / off["goodput_tok_s"], 3)}),
              flush=True)


if __name__ == "__main__":
    main()
