"""Autotuner measured-mode validation on real hardware (r4 verdict Weak #6:
the cost ordering had never touched real timings). Runs a measured tune —
stage x micro-batch ladder on the live backend, timings through the fused
``train_batches`` dispatch — and reports every measured candidate plus the
winner, so the ranking can be checked against the banked bench numbers
(350m mb=8 ~ 70 TFLOPS was the hand-found optimum; the tuner should agree
or beat it).

Run: python tools/tune_bench.py        (one process holds the chip)
Env: TUNE_MODEL=350m TUNE_SEQ=1024 TUNE_MAX_MBS=16 TUNE_STAGES=0,1
     TUNE_STEPS=6 (timed steps per candidate)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

MODEL = os.environ.get("TUNE_MODEL", "350m")
SEQ = int(os.environ.get("TUNE_SEQ", "1024"))
MAX_MBS = int(os.environ.get("TUNE_MAX_MBS", "16"))
STAGES = [int(s) for s in os.environ.get("TUNE_STAGES", "0,1").split(",")]
STEPS = int(os.environ.get("TUNE_STEPS", "6"))


def main():
    import jax

    from bench_core import enable_compile_cache, flops_per_token_from_cfg

    enable_compile_cache()
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config(MODEL, n_positions=SEQ, remat=True,
                          attention_backend="flash"
                          if jax.default_backend() == "tpu" else "xla",
                          dtype=jnp.bfloat16, vocab_size=50304,
                          embed_onehot_grad=True, fused_head_loss_chunk=1024)
    user_config = {
        "train_batch_size": jax.device_count(),  # rescaled per candidate
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": STAGES[0]},
        "steps_per_print": 10**9,
        "autotuning": {"enabled": True, "measure": True, "top_k": 3,
                       "zero_stages": STAGES,
                       "start_profile_step": 1, "end_profile_step": 1 + STEPS,
                       "max_train_micro_batch_size_per_gpu": MAX_MBS,
                       # default repo-relative dirs are the committed chip
                       # evidence — CI smoke runs redirect to a tmp dir so
                       # they never churn the banked artifacts
                       "results_dir": os.environ.get(
                           "TUNE_RESULTS_DIR", "autotuning_results"),
                       "exps_dir": os.environ.get(
                           "TUNE_EXPS_DIR", "autotuning_exps")},
    }
    rng = np.random.default_rng(0)
    example = {"input_ids": rng.integers(0, cfg.vocab_size,
                                         (jax.device_count(), SEQ)).astype(np.int32)}
    t0 = time.time()
    tuner = Autotuner(model=GPT2LMHeadModel(cfg), config=user_config,
                      example_batch=example)
    best = tuner.tune()
    fpt = flops_per_token_from_cfg(tuner.get_model_num_params() or 0, cfg, SEQ)
    rows = []
    for exp in tuner.records:
        row = {"name": exp.name, "status": exp.status,
               "metric_val": exp.metric_val}
        if exp.measured_step_s:
            tok = exp.micro_batch_size * SEQ / exp.measured_step_s
            row["measured_step_ms"] = round(exp.measured_step_s * 1e3, 1)
            row["measured_tflops"] = round(fpt * tok / 1e12, 2)
        rows.append(row)
    print(json.dumps({
        "backend": __import__("jax").default_backend(),
        "model": MODEL, "seq": SEQ, "elapsed_s": round(time.time() - t0, 1),
        "winner": best.name if best else None,
        "winner_measured_step_ms": (round(best.measured_step_s * 1e3, 1)
                                    if best and best.measured_step_s else None),
        "candidates": rows,
    }), flush=True)


if __name__ == "__main__":
    main()
