"""Benchmark: GPT-2 causal-LM training throughput on one TPU chip.

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline"}.

``vs_baseline`` is achieved model TFLOP/s per chip divided by the
reference's headline per-device training throughput claim (64 TFLOP/s per
V100, BERT-large pretrain — BASELINE.md / reference
``docs/_posts/2020-05-28-fastest-bert-training.md:13``). Model FLOPs use
the standard 6*N*T causal-LM estimate.

Structure: one process for the chip at a time.

- the parent never imports jax, so it never holds the chip; it runs its
  children one after another, each under a wall-clock budget: a probe
  that requires a TPU and compiles one tiny program, then the benchmark,
  then (BENCH_PARITY=1) the parity curves;
- a run whose probe or benchmark child finds no TPU, fails or times out
  exits non-zero and prints no metric line. There is no CPU fallback: a
  CPU timing is never printed under a device metric's name;
- the result line names the device it ran on (platform, device_kind,
  device count).

Tunables: BENCH_MODEL / BENCH_MICRO_BS / BENCH_SEQ / BENCH_STEPS and
BENCH_PROBE_TIMEOUT / BENCH_RUN_TIMEOUT (seconds).
"""

import json
import os
import subprocess
import sys
import time

BASELINE_TFLOPS = 64.0  # reference headline, BASELINE.md


def _require_tpu():
    """The devices, or SystemExit when jax reports anything but a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: jax found no TPU (platform "
                         f"{devices[0].platform!r}); no metric is printed")
    return devices


# --------------------------------------------------------------------------
# child: the actual benchmark (runs in a subprocess; may crash or hang —
# the parent owns the timeout)
# --------------------------------------------------------------------------

def run_child():
    import numpy as np
    import jax

    devices = _require_tpu()
    # persistent compile cache: repeat bench runs skip straight to execution
    from envutil import use_compile_cache
    use_compile_cache()

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    import jax.numpy as jnp

    model_name = os.environ.get("BENCH_MODEL", "350m")
    # mb=8 measured fastest on v5e (69-75 TFLOPS/chip vs 62 at mb=4; mb=16
    # OOMs) — a sweep that predates PR 5
    micro_bs = int(os.environ.get("BENCH_MICRO_BS", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "60"))
    # remat measured slightly faster at this size on v5e (415.7 vs 425.3 ms
    # per step, r3 sweep) — the step is memory-bound, so trading HBM traffic
    # for recompute wins
    remat = os.environ.get("BENCH_REMAT", "1") == "1"

    n_dev = jax.device_count()
    attn = os.environ.get("BENCH_ATTN", "flash")
    # compute in bf16 end-to-end: without an explicit dtype the flax modules
    # force fp32 compute even though the engine casts params to bf16
    overrides = {}
    # vocab padded to a lane-aligned multiple (Megatron-style): 50257 → 50304
    # tiles the LM-head matmul cleanly on the MXU. Both this and the
    # scatter-free embedding backward measured faster on v5e (r3 sweep:
    # 68.2 → 75.0 TFLOPS at mb=8) — on by default, opt out with "0"/"".
    vocab_override = int(os.environ.get("BENCH_VOCAB", "50304") or 0)
    if vocab_override > 0:
        overrides["vocab_size"] = vocab_override
    if os.environ.get("BENCH_EMBED_ONEHOT", "1") == "1":
        overrides["embed_onehot_grad"] = True
    # chunked fused LM-head loss (no [B,L,V] logits buffer) — measured
    # faster than the plain head at mb=8 on v5e (70.1 vs 69.0 TFLOPS,
    # before PR 5) — on by default, opt out with "0"
    if os.environ.get("BENCH_FUSED_XENT", "1") == "1":
        overrides["fused_head_loss_chunk"] = int(os.environ.get("BENCH_XENT_CHUNK", "1024"))
    cfg_model = get_gpt2_config(model_name, n_positions=seq, remat=remat,
                                attention_backend=attn, dtype=jnp.bfloat16,
                                **overrides)
    model = GPT2LMHeadModel(cfg_model)

    zero_stage = int(os.environ.get("BENCH_ZERO", "1" if n_dev > 1 else "0"))
    zero_cfg = {"stage": zero_stage}
    # BENCH_OFFLOAD=1: the ZeRO-Infinity recipe (stage 3 + host-resting
    # streamed params + host C++ Adam) — the quick on-chip A/B for the
    # offload path's overhead vs the dense step
    if os.environ.get("BENCH_OFFLOAD", "0") == "1":
        zero_cfg = {"stage": 3,
                    "offload_param": {"device": "cpu", "pin_memory": True},
                    "offload_optimizer": {"device": "cpu", "pin_memory": True}}
    ds_config = {
        "train_batch_size": micro_bs * n_dev,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": zero_cfg,
        "steps_per_print": 10**9,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config)

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg_model.vocab_size,
                                       (micro_bs * n_dev, seq)).astype(np.int32)}

    engine.initialize_state(batch)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.state.params))

    # >1: run that many optimizer steps per device dispatch (lax.scan inside
    # one jit call) — amortizes host→device dispatch latency, the idiomatic
    # TPU training-loop shape. A scanned program that fails to build fails
    # the run.
    fused = int(os.environ.get("BENCH_FUSED_STEPS", "30"))
    fused = max(1, min(fused, steps))  # BENCH_STEPS=10 means 10 steps, not 30
    if fused > 1:
        stack = {"input_ids": np.broadcast_to(batch["input_ids"],
                                              (fused,) + batch["input_ids"].shape)}
        engine.train_batches(stack)  # warmup/compile
        jax.block_until_ready(engine.state.params)
        outer = max(1, steps // fused)
        t0 = time.time()
        for _ in range(outer):
            engine.train_batches(stack)
        jax.block_until_ready(engine.state.params)
        dt = time.time() - t0
        steps = outer * fused
    else:
        for _ in range(2):  # warmup/compile
            engine.train_batch(batch)
        jax.block_until_ready(engine.state.params)
        t0 = time.time()
        for _ in range(steps):
            engine.train_batch(batch)
        jax.block_until_ready(engine.state.params)
        dt = time.time() - t0

    tokens = micro_bs * n_dev * seq * steps
    tok_per_sec_chip = tokens / dt / n_dev
    # FLOPs/token = 6N + causal attention term (6*L*s*hidden) — the bare 6N
    # estimate omits the O(L^2) score matmuls and understates long-context
    # MFU by up to ~2x at seq=8k (tools/bench_core.model_flops_per_token)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    from bench_core import flops_per_token_from_cfg
    fpt = flops_per_token_from_cfg(n_params, cfg_model, seq)
    model_tflops = fpt * tok_per_sec_chip / 1e12
    print(json.dumps({
        "metric": f"gpt2_{model_name}_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(model_tflops / BASELINE_TFLOPS, 4),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "tflops_per_chip": round(model_tflops, 2),
        "n_params": n_params,
        "step_ms": round(dt / steps * 1e3, 1),
        "attn_flops_frac": round(1.0 - 6.0 * n_params / fpt, 3),
    }))


def run_parity():
    """Emit this backend's reproducible loss curve (tools/parity_check)."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    import parity_check
    parity_check.main()


def run_probe():
    """Tiny end-to-end check that the TPU can init AND compile."""
    import jax
    import jax.numpy as jnp

    devices = _require_tpu()
    out = jax.jit(lambda x: x * 2.0 + 1.0)(jnp.float32(20.5))
    assert float(out) == 42.0
    print(f"probe ok: {len(devices)} {devices[0].device_kind} device(s)", flush=True)


# --------------------------------------------------------------------------
# parent orchestration (never imports jax)
# --------------------------------------------------------------------------

def _run(mode, env, timeout):
    """Run this file in `mode` as a subprocess. Returns (rc, stdout, stderr);
    rc=124 on timeout."""
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode],
            env=env, capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        return p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        from envutil import to_text
        return 124, to_text(e.stdout), to_text(e.stderr)


def _parity_report(timeout):
    """BASELINE north star: accelerator-vs-CPU loss-curve parity. Runs the
    reproducible curve (tools/parity_check) once on the accelerator and
    once in a CPU-pinned subprocess, and reports bit-identity / max-ULP.
    Failures degrade to an explanatory dict — parity must never cost the
    bench its throughput number."""
    try:
        rc_a, out_a, err_a = _run("parity", dict(os.environ), timeout)
        a = _last_json_line(out_a)
        if rc_a != 0 or a is None:
            return {"error": f"accel curve rc={rc_a}: "
                    f"{err_a.strip().splitlines()[-1] if err_a.strip() else 'no output'}"}
        from envutil import cpu_subprocess_env
        # one pinned CPU device: the curve's workload is single-device by
        # construction (parity_check.curve), keep the device count fixed too
        rc_c, out_c, err_c = _run("parity", cpu_subprocess_env(n_virtual_devices=1), timeout)
        c = _last_json_line(out_c)
        if rc_c != 0 or c is None:
            return {"error": f"cpu curve rc={rc_c}: "
                    f"{err_c.strip().splitlines()[-1] if err_c.strip() else 'no output'}"}
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
        import parity_check
        rep = parity_check.compare(parity_check.from_hex(a["curve_hex"]),
                                   parity_check.from_hex(c["curve_hex"]))
        rep["backends"] = [a.get("backend"), c.get("backend")]
        envelope = int(os.environ.get("PARITY_MAX_ULP", "0"))
        rep["within_envelope"] = rep["max_ulp"] <= envelope or rep["bit_identical"]
        rep["envelope_ulp"] = envelope
        # the accelerator curve's per-(src->dst, scope) upcast inventory
        # (R002 via tools/parity_check) rides along so a refused bank
        # carries its own ULP-hunt evidence
        if a.get("precision_attribution") is not None:
            rep["precision_attribution"] = a["precision_attribution"]
        return rep
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}


def _attribution_by_scope(attribution):
    """Collapse R002's ``"src->dst @ scope": count`` tally to per-scope
    totals — the compact summary a refused bank records (which scopes
    widen, not every op instance)."""
    by_scope = {}
    for key, count in (attribution or {}).items():
        if not isinstance(count, int):
            continue  # error dicts degrade to empty
        scope = key.split("@", 1)[1].strip() if "@" in key else key
        by_scope[scope] = by_scope.get(scope, 0) + count
    return dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))


def _apply_parity_bank_gate(result, banked_path):
    """ROADMAP item 4, last clause: a round whose parity phase reports
    ``within_envelope: false`` must not bank its throughput number
    silently. The refusal (or the explicit ``PARITY_BANK_ANYWAY=1``
    override) and a per-scope ``precision_attribution`` summary are
    recorded in the bench JSON either way, so every banked number carries
    its parity verdict. Returns True when the banked number survives."""
    par = result.get("parity") or {}
    if par.get("within_envelope") is not False:
        return True
    gate = {
        "within_envelope": False,
        "max_ulp": par.get("max_ulp"),
        "envelope_ulp": par.get("envelope_ulp"),
        "precision_attribution_by_scope":
            _attribution_by_scope(par.get("precision_attribution")),
    }
    if os.environ.get("PARITY_BANK_ANYWAY", "0") == "1":
        gate["banked_anyway"] = True
        result["parity_bank"] = gate
        print("# parity outside envelope; banking anyway (PARITY_BANK_ANYWAY=1)",
              flush=True)
        return True
    gate["refused"] = ("parity within_envelope=false — throughput number not "
                       "banked; set PARITY_BANK_ANYWAY=1 to override")
    result["parity_bank"] = gate
    try:
        os.unlink(banked_path)
    except OSError:
        pass
    print(f"# BANK REFUSED: {gate['refused']}", flush=True)
    return False


def _last_json_line(text):
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _last_line(text):
    text = (text or "").strip()
    return text.splitlines()[-1] if text else "no output"


def main():
    # run budget sized for a COLD compile cache: the fused-scan 350M
    # program (scan length doesn't change program size) compiles for
    # minutes. The compile cache (envutil.use_compile_cache) makes warm
    # runs skip it.
    probe_timeout = int(os.environ.get("BENCH_PROBE_TIMEOUT", "120"))
    run_timeout = int(os.environ.get("BENCH_RUN_TIMEOUT", "2400"))
    banked = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".bench_banked.json")
    # clear the previous run's banked number: a stale file would be read
    # as this run's
    try:
        os.unlink(banked)
    except OSError:
        pass

    # 1) the probe child: a TPU that initialises and compiles, or no run
    rc, out, err = _run("probe", dict(os.environ), probe_timeout)
    if rc != 0:
        print(f"bench: probe failed rc={rc}: {_last_line(err or out)}", file=sys.stderr)
        return 1

    # 2) the benchmark child (the probe has exited: the chip is free)
    rc, out, err = _run("child", dict(os.environ), run_timeout)
    result = _last_json_line(out)
    if rc != 0 or result is None:
        print(f"bench: benchmark child failed rc={rc}: {_last_line(err)}", file=sys.stderr)
        return 1

    # bank the throughput number BEFORE the parity phase (which runs two
    # more training subprocesses, up to 2x BENCH_PARITY_TIMEOUT): a
    # parity-phase hang must never cost the run its number
    try:
        with open(banked, "w") as f:
            json.dump(result, f)
    except OSError:
        pass
    print(f"# banked pre-parity: {json.dumps(result)}", flush=True)
    if os.environ.get("BENCH_PARITY", "1") == "1":
        result["parity"] = _parity_report(
            int(os.environ.get("BENCH_PARITY_TIMEOUT", "600")))
        # an out-of-envelope run un-banks the pre-parity number
        # (ROADMAP 4: determinism is a product feature, not a
        # footnote) — the JSON line still reports everything
        _apply_parity_bank_gate(result, banked)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "child":
        run_child()
    elif len(sys.argv) > 1 and sys.argv[1] == "probe":
        run_probe()
    elif len(sys.argv) > 1 and sys.argv[1] == "parity":
        run_parity()
    else:
        sys.exit(main())
