"""Fault-injection bench: inject each documented failure class and assert
the documented recovery (runtime/resilience). CPU-only by design — the
recovery *logic* is backend-independent, and proving it must never burn a
chip window. One JSON row per scenario; exit 1 if any recovery contract
fails.

| fault class            | injection                                   | documented recovery                          |
|------------------------|---------------------------------------------|----------------------------------------------|
| torn save (crash)      | SIGKILL between staging and atomic rename   | partial tag invisible; previous tag loads    |
| truncated checkpoint   | truncate largest manifest-listed file       | verified fallback to newest intact tag       |
| bit-flipped checkpoint | flip one bit in array data                  | verified fallback to newest intact tag       |
| persistent NaN grads   | inf loss boost through real overflow path   | abort after K consecutive skips (loud)       |
| SIGKILL mid-run        | DS_FAULT_SPEC step=sigkill@N under agent    | restart + bit-exact resumed loss curve       |
| transient backend loss | backend-unavailable-shaped flaky call       | retried with backoff; attempts in evidence   |
| SIGTERM mid-serve      | real SIGTERM to a serving subprocess        | in-flight drained to full budget, queue      |
|                        |                                             | refused, exit 143 (graft-serve drain)        |
| scale-up (4 -> 8)      | SIGKILL at step k on 4 virtual devices,     | resume_elastic reshards the verified         |
|                        | agent relaunches on 8 (graft-elastic)       | checkpoint; curve in envelope; W->W'->W      |
|                        |                                             | leaf digests bit-identical                   |
| scale-down (4 -> 2)    | same, relaunched on 2 virtual devices       | same contract in the gather direction        |
| SIGTERM fleet replica  | sigterm one of two router-driven replicas   | in-flight KV migrates to the peer through a  |
|                        | mid-flight (graft-fleet)                    | digest-verified bundle; zero dropped; greedy |
|                        |                                             | parity with an uninterrupted run             |
| SIGKILL fleet replica  | hard-kill a replica, no drain, no bundle    | router re-admits orphaned requests on the    |
|                        |                                             | peer at-most-once; zero dropped; bounded     |
|                        |                                             | TTFT spike                                   |
| SIGTERM mid RLHF loop  | real SIGTERM after >=1 learner step of the  | in-flight rollouts drained + banked (zero    |
|                        | in-flight rollout loop (graft-rlhf)         | dropped), learner checkpoints at a boundary, |
|                        |                                             | resumed run stitches the loss curve within   |
|                        |                                             | RLHF_STITCH_LOSS_RTOL of uninterrupted       |

Run: python tools/fault_bench.py            (scenario subset: FAULT_SCENARIOS=...)
Tests import the scenario functions directly (tests/unit/resilience/).
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PY = sys.executable

# -- shared tiny-engine builder (in-process scenarios) -----------------------

def _tiny_engine(ds_extra=None, loss_fn=None):
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "steps_per_print": 1}
    ds.update(ds_extra or {})
    cfg = get_gpt2_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg),
                                               config=ds, loss_fn=loss_fn)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 16)).astype(np.int32)}
    return engine, batch


def _row(fault, expected, observed, ok, **extra):
    return dict({"fault": fault, "expected": expected, "observed": observed,
                 "ok": bool(ok)}, **extra)


# -- corruption scenarios (in-process) ---------------------------------------

def scenario_corrupt_checkpoint(workdir, mode="truncate"):
    """Damage the newest tag; load must fall back to the previous intact one
    — not crash, not silently load garbage."""
    from deepspeed_tpu.runtime.resilience.faults import corrupt_checkpoint
    ckpt = os.path.join(workdir, f"ckpt_{mode}")
    engine, batch = _tiny_engine()
    engine.train_batch(batch)
    engine.save_checkpoint(ckpt, tag="t1")
    engine.train_batch(batch)
    engine.save_checkpoint(ckpt, tag="t2")
    corrupt_checkpoint(ckpt, "t2", mode=mode)
    fresh, _ = _tiny_engine()
    fresh.initialize_state(batch)
    fresh.load_checkpoint(ckpt)
    loaded = getattr(fresh, "_loaded_checkpoint_tag", None)
    return _row(f"{mode}_checkpoint", "fallback to t1", f"loaded {loaded}",
                loaded == "t1" and fresh.global_steps == 1)


def scenario_all_corrupt(workdir):
    """Every tag damaged: the failure must be LOUD (CheckpointCorruptError),
    never a silent load of garbage params."""
    from deepspeed_tpu.runtime.resilience.faults import corrupt_checkpoint
    from deepspeed_tpu.runtime.resilience.manifest import CheckpointCorruptError
    ckpt = os.path.join(workdir, "ckpt_all_corrupt")
    engine, batch = _tiny_engine()
    engine.train_batch(batch)
    engine.save_checkpoint(ckpt, tag="only")
    corrupt_checkpoint(ckpt, "only", mode="bitflip")
    fresh, _ = _tiny_engine()
    fresh.initialize_state(batch)
    try:
        fresh.load_checkpoint(ckpt)
        observed = "loaded silently"
    except CheckpointCorruptError as e:
        observed = f"raised CheckpointCorruptError: {str(e)[:80]}"
    return _row("all_tags_corrupt", "loud CheckpointCorruptError",
                observed, observed.startswith("raised"))


# -- poisoned numerics -------------------------------------------------------

def scenario_overflow_abort(workdir, abort_after=3):
    """Persistent non-finite gradients: K consecutive overflow-skips must
    abort the run (fail fast), through the REAL grad/overflow machinery."""
    from deepspeed_tpu.runtime.fp16.loss_scaler import OverflowAbort
    from deepspeed_tpu.runtime.resilience.faults import overflow_injected_loss, poison_batch
    engine, batch = _tiny_engine(
        ds_extra={"resilience": {"max_consecutive_overflows": abort_after}},
        loss_fn=overflow_injected_loss())
    engine.train_batch(batch)  # healthy step first: streak must start at the poison
    poisoned = poison_batch(batch)
    steps_survived = 0
    observed = f"no abort after {abort_after + 2} poisoned steps"
    try:
        for _ in range(abort_after + 2):
            engine.train_batch(poisoned)
            steps_survived += 1
    except OverflowAbort as e:
        observed = f"OverflowAbort after {steps_survived + 1} poisoned steps: {str(e)[:60]}"
    return _row("persistent_nan_grads", f"OverflowAbort after {abort_after} skips",
                observed, steps_survived + 1 == abort_after and "OverflowAbort" in observed,
                skipped_total=int(engine._skipped_steps))


# -- transient infrastructure ------------------------------------------------

def scenario_http500_retry(workdir, fails=2):
    """Transient backend-unavailable failures: retried with backoff, each
    attempt in the evidence row (the exact message text the installed
    runtime raises)."""
    from deepspeed_tpu.runtime.resilience.faults import FlakyCall
    from deepspeed_tpu.runtime.resilience.retry import BACKEND_UNAVAILABLE, RetryPolicy
    flaky = FlakyCall(lambda: "banked", fails=fails)
    policy = RetryPolicy(max_attempts=fails + 1, base_delay=0.01, jitter=0.25,
                         seed=0, sleep=lambda s: None)
    result = policy.call(flaky)
    ev = policy.evidence()
    ok = (result == "banked" and flaky.calls == fails + 1
          and ev.get("retries") == fails
          and all(a["error_class"] == BACKEND_UNAVAILABLE for a in ev["retry_history"]))
    return _row("transient_http500", f"success after {fails} retries, history recorded",
                f"result={result!r} calls={flaky.calls}", ok, **ev)


# -- process-death scenarios (subprocess) ------------------------------------

_TORN_SAVE_CHILD = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache; use_compile_cache()
    import numpy as np, deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    cfg = get_gpt2_config("test")
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        config={{"train_batch_size": 8,
                 "optimizer": {{"type": "Adam", "params": {{"lr": 1e-3}}}}}})
    batch = {{"input_ids": np.zeros((8, 16), np.int32)}}
    eng.train_batch(batch)
    eng.save_checkpoint({ckpt!r}, tag="good")
    eng.train_batch(batch)
    os.environ["DS_FAULT_SPEC"] = "ckpt_pre_rename=sigkill"   # die mid-publish
    eng.save_checkpoint({ckpt!r}, tag="torn")
    print("UNREACHABLE")
""")


def scenario_torn_save(workdir):
    """SIGKILL between checkpoint staging and the atomic rename: the torn
    tag must be INVISIBLE (staging dir only), 'latest' still names the
    previous tag, and a fresh engine loads it cleanly."""
    from envutil import cpu_subprocess_env
    ckpt = os.path.join(workdir, "ckpt_torn")
    p = subprocess.run([PY, "-c", _TORN_SAVE_CHILD.format(repo=REPO, ckpt=ckpt)],
                       env=cpu_subprocess_env(), capture_output=True, text=True,
                       timeout=420, cwd=REPO)
    killed = p.returncode == -9 and "UNREACHABLE" not in p.stdout
    entries = sorted(os.listdir(ckpt)) if os.path.isdir(ckpt) else []
    torn_invisible = "torn" not in entries and ".tmp.torn" in entries
    latest_ok = open(os.path.join(ckpt, "latest")).read().strip() == "good"
    # recovery leg: a fresh engine resumes from 'good' and its next save
    # sweeps the stale staging dir
    fresh, batch = _tiny_engine()
    fresh.initialize_state(batch)
    fresh.load_checkpoint(ckpt)
    resumed_ok = fresh._loaded_checkpoint_tag == "good" and fresh.global_steps == 1
    fresh.save_checkpoint(ckpt, tag="after")
    swept = ".tmp.torn" not in os.listdir(ckpt)
    return _row("torn_save_sigkill",
                "partial tag invisible; latest->good; resume ok; staging swept",
                f"killed={killed} entries={entries} resumed={fresh._loaded_checkpoint_tag} "
                f"swept={swept}",
                killed and torn_invisible and latest_ok and resumed_ok and swept)


_TRAIN_CHILD = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache; use_compile_cache()
    import numpy as np, jax.numpy as jnp, deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    restarted = os.environ.get("DS_ELASTIC_RESTART_COUNT", "0") != "0"
    if restarted:
        os.environ.pop("DS_FAULT_SPEC", None)   # fault fires on the first life only
    cfg = get_gpt2_config("test", n_layer=2)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        config={{"train_batch_size": 8,
                 "optimizer": {{"type": "Adam", "params": {{"lr": 1e-3}}}}}})
    eng.initialize_state({{"input_ids": np.zeros((8, 16), np.int32)}})
    eng.resume({ckpt!r})     # fresh start on the first life, verified resume after
    while eng.global_steps < {total}:
        step = eng.global_steps
        rng = np.random.RandomState(1000 + step)
        batch = {{"input_ids": rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)}}
        loss = float(jnp.asarray(eng.train_batch(batch)))
        with open({losses!r}, "a") as f:
            f.write(json.dumps({{"step": step, "loss": loss.hex()}}) + chr(10))
        eng.save_checkpoint({ckpt!r})
        from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat
        touch_heartbeat(payload={{"global_step": eng.global_steps,
                                  "last_span": "checkpoint"}})
    print("CHILD_DONE", eng.global_steps)
""")


def run_supervised(workdir, name, total, fault_env):
    """One supervised training run (DSElasticAgent around a CPU child that
    trains ``total`` steps with per-step deterministic data, checkpointing
    and resuming via engine.resume). Returns ``(rc, agent, {step: loss_hex})``
    — losses as exact float hex so comparisons are bit-level, not approx."""
    from envutil import cpu_subprocess_env
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent

    d = os.path.join(workdir, name)
    os.makedirs(d, exist_ok=True)
    losses = os.path.join(d, "losses.jsonl")
    child = _TRAIN_CHILD.format(repo=REPO, ckpt=os.path.join(d, "ckpt"),
                                losses=losses, total=total)
    env = cpu_subprocess_env()
    env.update(fault_env)
    agent = DSElasticAgent([PY, "-c", child], world_sizes=[1],
                           heartbeat_timeout=300.0, max_restarts=1, env=env)
    rc = agent.run(workdir=d)
    rows = [json.loads(l) for l in open(losses)] if os.path.exists(losses) else []
    return rc, agent, {r["step"]: r["loss"] for r in rows}


def scenario_sigkill_resume(workdir, kill_at=2, total=4):
    """SIGKILL at a step boundary under DSElasticAgent: the agent restarts
    the child, resume() restores the timeline, and the stitched loss curve
    is BIT-identical to an uninterrupted run (losses compared as exact
    float hex)."""
    rc, agent, losses = run_supervised(workdir, "faulted", total,
                                       {"DS_FAULT_SPEC": f"step=sigkill@{kill_at}"})
    ref_rc, _, ref_losses = run_supervised(workdir, "reference", total, {})
    bit_exact = (losses == ref_losses and len(ref_losses) == total)
    # how far each attempt got, from the heartbeat payload the agent
    # snapshots at attempt end (not just that the child was alive)
    progress = [h.get("last_heartbeat") for h in agent.history]
    return _row("sigkill_midrun_resume",
                f"agent restart + bit-exact {total}-step curve",
                f"rc={rc} restarts={agent.restart_count} steps={sorted(losses)} "
                f"bit_exact={bit_exact} progress={progress}",
                rc == 0 and ref_rc == 0 and agent.restart_count == 1 and bit_exact,
                attempt_progress=progress)


# -- elastic resharding scenarios (graft-elastic: subprocess, world change) --

#: documented loss-curve envelope for a world-size change: the stitched
#: post-reshard curve vs the uninterrupted fixed-world reference. Data and
#: RNG are step-deterministic and the restored leaves are digest-proven
#: bit-identical, so the only drift source is cross-world reduction order
#: (fp32 on CPU) — same envelope the cross-world elasticity test has
#: carried since PR 4 (tests/unit/elasticity/test_elastic_agent.py).
RESHARD_LOSS_RTOL = 2e-4

_ELASTIC_CHILD = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo!r})
    world = int(os.environ["DS_ELASTIC_WORLD_SIZE"])
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(f for f in os.environ.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={{world}}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache; use_compile_cache()
    import numpy as np, jax.numpy as jnp, deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    if os.environ.get("DS_ELASTIC_RESTART_COUNT", "0") != "0":
        os.environ.pop("DS_FAULT_SPEC", None)   # fault fires on the first life only
    cfg = get_gpt2_config("test", n_layer=2)
    # stage 3 + persistence threshold 0: every param fsdp-sharded, so a
    # world change genuinely re-chunks the whole state
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), topology=MeshTopology(fsdp=world),
        config={{"train_batch_size": 8,
                 "optimizer": {{"type": "Adam", "params": {{"lr": 1e-3}}}},
                 "zero_optimization": {{"stage": 3,
                                        "stage3_param_persistence_threshold": 0}}}})
    eng.initialize_state({{"input_ids": np.zeros((8, 16), np.int32)}})
    report = eng.resume_elastic({ckpt!r})   # fresh / plain / reshard by topology
    with open({modes!r}, "a") as f:
        f.write(json.dumps({{"world": world, "mode": report.mode, "tag": report.tag,
                             "gather_bytes": report.gather_bytes}}) + chr(10))
    rt = os.environ.get("DS_ROUNDTRIP_TAG")
    if rt:   # round-trip probe: re-save the resumed state untouched, then exit
        eng.save_checkpoint({ckpt!r}, tag=rt, save_latest=False)
        print("ROUNDTRIP_SAVED", rt)
        sys.exit(0)
    while eng.global_steps < {total}:
        step = eng.global_steps
        rng = np.random.RandomState(1000 + step)
        batch = {{"input_ids": rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)}}
        loss = float(jnp.asarray(eng.train_batch(batch)))
        with open({losses!r}, "a") as f:
            f.write(json.dumps({{"step": step, "world": world, "loss": loss.hex()}}) + chr(10))
        eng.save_checkpoint({ckpt!r})
        from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat
        touch_heartbeat(payload={{"global_step": eng.global_steps,
                                  "last_span": "checkpoint"}})
    print("CHILD_DONE", eng.global_steps)
""")


def run_elastic(workdir, name, total, fault_env, world_sizes, roundtrip_tag=None):
    """One supervised ELASTIC run: DSElasticAgent around a CPU child that
    pins its own virtual-device count to ``DS_ELASTIC_WORLD_SIZE``, trains
    with per-step deterministic data, and comes up through
    ``resume_elastic``. Returns ``(rc, agent, {step: loss_hex}, modes)``
    where ``modes`` records each life's resume decision."""
    from envutil import cpu_subprocess_env
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent

    d = os.path.join(workdir, name)
    os.makedirs(d, exist_ok=True)
    ckpt = os.path.join(d, "ckpt")
    losses = os.path.join(d, "losses.jsonl")
    modes = os.path.join(d, "modes.jsonl")
    child = _ELASTIC_CHILD.format(repo=REPO, ckpt=ckpt, losses=losses,
                                  modes=modes, total=total)
    env = cpu_subprocess_env()
    env.pop("XLA_FLAGS", None)  # the child pins its own device count
    env.update(fault_env)
    if roundtrip_tag:
        env["DS_ROUNDTRIP_TAG"] = roundtrip_tag
    agent = DSElasticAgent([PY, "-c", child], world_sizes=list(world_sizes),
                           heartbeat_timeout=300.0, max_restarts=1, env=env,
                           checkpoint_dir=ckpt)
    rc = agent.run(workdir=d)
    rows = [json.loads(l) for l in open(losses)] if os.path.exists(losses) else []
    mode_rows = [json.loads(l) for l in open(modes)] if os.path.exists(modes) else []
    return rc, agent, {r["step"]: r["loss"] for r in rows}, mode_rows


_ELASTIC_REF = {}  # total -> {step: loss_hex} (shared fixed-world-4 reference)


def _elastic_reference(workdir, total):
    """Uninterrupted world-4 reference run (shared by scale_up/scale_down —
    one subprocess life per bench process)."""
    if total not in _ELASTIC_REF:
        rc, _, losses, modes = run_elastic(workdir, f"ref4_{total}", total, {}, [4])
        assert rc == 0 and modes[0]["mode"] == "fresh", (rc, modes)
        _ELASTIC_REF[total] = losses
    return _ELASTIC_REF[total]


def _manifest_digests(ckpt, tag):
    with open(os.path.join(ckpt, tag, "manifest.json")) as f:
        leaves = json.load(f)["leaves"]
    return {k: v["sha256"] for k, v in leaves.items()}


def scenario_scale(workdir, new_world, kill_at=2, total=3):
    """SIGKILL at step ``kill_at`` on 4 virtual devices; the elastic agent
    relaunches at ``new_world``; ``resume_elastic`` reshards the verified
    checkpoint onto the new mesh. Asserts: (a) the relaunched life reports
    mode=reshard with nonzero gather bytes and the agent's history row
    records the 4 -> ``new_world`` transition; (b) pre-kill steps are
    BIT-identical to the fixed-world reference and post-reshard steps stay
    inside :data:`RESHARD_LOSS_RTOL`; (c) a world-4 round-trip probe
    (W -> W' -> W) re-saves leaf digests bit-identical to the final W'
    checkpoint — the reshard moved every byte and invented none."""
    name = f"scale_{new_world}"
    rc, agent, losses, modes = run_elastic(
        workdir, name, total, {"DS_FAULT_SPEC": f"step=sigkill@{kill_at}"},
        [4, new_world])
    ref = _elastic_reference(workdir, total)
    ok = rc == 0 and agent.restart_count == 1 and agent.history[0]["rc"] == -9
    complete = sorted(losses) == list(range(total)) and len(modes) == 2
    if complete:
        ok = ok and modes[0]["mode"] == "fresh" and modes[1]["mode"] == "reshard" \
            and modes[1]["gather_bytes"] > 0
    else:
        ok = False
    topo = (agent.history[1].get("topology") or {}) if len(agent.history) > 1 else {}
    ok = ok and topo.get("resume") == "reshard" and topo.get("ckpt_world") == 4 \
        and topo.get("world_size") == new_world and topo.get("prev_world_size") == 4
    # documented envelope: bit-exact before the kill (steps the first,
    # world-4 life completed), RESHARD_LOSS_RTOL after the reshard. The
    # life-1 step interrupted mid-train (kill_at-1) is REPLAYED by the
    # resharded life, so it belongs to the envelope side.
    env_ok, worst = complete, 0.0
    for step in range(total) if complete else ():
        got, want = float.fromhex(losses[step]), float.fromhex(ref[step])
        if step < kill_at - 1:
            env_ok = env_ok and losses[step] == ref[step]
        else:
            rel = abs(got - want) / max(abs(want), 1e-12)
            worst = max(worst, rel)
            env_ok = env_ok and rel <= RESHARD_LOSS_RTOL
    # round-trip leg: resume the final W' checkpoint back at world 4 and
    # compare per-leaf digests — bit-identity through W -> W' -> W
    digests_match = False
    if ok and env_ok:
        ckpt = os.path.join(workdir, name, "ckpt")
        rt_rc, _, _, rt_modes = run_elastic(workdir, name, total, {}, [4],
                                            roundtrip_tag="roundtrip")
        digests_match = (rt_rc == 0 and rt_modes[-1]["mode"] == "reshard"
                         and _manifest_digests(ckpt, f"global_step{total}")
                         == _manifest_digests(ckpt, "roundtrip"))
    ok = ok and env_ok and digests_match
    return _row(f"scale_4_to_{new_world}",
                f"reshard resume + curve in {RESHARD_LOSS_RTOL} envelope + "
                f"W->W'->W digests identical",
                f"rc={rc} modes={[m['mode'] for m in modes]} "
                f"gather={modes[1]['gather_bytes'] if len(modes) > 1 else None} "
                f"worst_rel={worst:.2e} digests_match={digests_match} topo={topo}",
                ok, attempt_topology=topo)


def scenario_scale_up(workdir):
    return scenario_scale(workdir, new_world=8)


def scenario_scale_down(workdir):
    return scenario_scale(workdir, new_world=2)


_SERVE_CHILD = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache; use_compile_cache()
    import numpy as np
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 Request, ServingConfig)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    cfg = get_gpt2_config("test", n_layer=2, n_positions=256)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    engine = InferenceEngine(GPT2LMHeadModel(cfg),
                             DeepSpeedInferenceConfig(replace_with_kernel_inject=False),
                             topology=topo)
    sched = ContinuousBatchingScheduler(engine,
                                        ServingConfig(slots=2, prefill_chunk=8))
    rng = np.random.default_rng(0)
    # ~190 warm decode ticks per slot pair: the full serve takes seconds,
    # so the parent's SIGTERM reliably lands mid-flight, while the
    # post-signal drain (<= one request's remaining budget) stays short
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32),
                    max_new_tokens=192) for _ in range(8)]
    # warm the serving programs so the post-signal drain measures the drain,
    # not XLA compiles
    warm = Request(prompt=reqs[0].prompt, max_new_tokens=2)
    sched.submit(warm)
    sched.run_until_drained(max_ticks=10**5)
    sched.finished.clear()
    print("SERVING_READY", flush=True)
    rc = sched.serve(reqs)           # installs the PreemptionGuard itself
    stats = sched.stats()
    print("DRAIN " + json.dumps({{
        "rc": rc, "finished": stats["finished"], "refused": stats["refused"],
        "in_flight_after": len(sched.in_flight),
        "pool_used_after": stats["pool"]["used_blocks"],
        "full_budget": all(len(r.output) == r.max_new_tokens
                           for r in sched.finished)}}), flush=True)
    sys.exit(rc)
""")


def scenario_serve_drain(workdir):
    """Real SIGTERM to an actively-serving process (graft-serve): in-flight
    requests must DRAIN to their full token budget (never truncated or
    dropped), everything still queued is terminally refused, no KV block
    leaks, and the process exits 143 so a supervisor reads preemption."""
    import select as _select
    import signal as _signal
    import time as _time

    from envutil import cpu_subprocess_env
    # stderr to a FILE, not a pipe: the parent tails stdout line-by-line
    # before SIGTERM, and an undrained stderr pipe filling up (verbose jax
    # warnings) would deadlock child against parent with no timeout armed
    err_path = os.path.join(workdir, "serve_drain.stderr")
    with open(err_path, "w") as err_fh:
        p = subprocess.Popen([PY, "-c", _SERVE_CHILD.format(repo=REPO)],
                             env=cpu_subprocess_env(), stdout=subprocess.PIPE,
                             stderr=err_fh, text=True, cwd=REPO)
        try:
            deadline = _time.monotonic() + 300
            ready = False
            # read the fd RAW while waiting: select() on the buffered
            # TextIOWrapper can report not-ready while SERVING_READY
            # already sits in the wrapper's internal buffer (a readline
            # drains every line the pipe delivered in one read)
            fd = p.stdout.fileno()
            os.set_blocking(fd, False)
            buf = b""
            while _time.monotonic() < deadline:
                if not _select.select([fd], [], [], 1.0)[0]:
                    continue
                chunk = os.read(fd, 65536)
                if not chunk:
                    break  # EOF: child died before serving
                buf += chunk
                if b"SERVING_READY" in buf:
                    ready = True
                    break
            os.set_blocking(fd, True)  # communicate() needs blocking reads
            if not ready:
                p.kill()
                p.wait(timeout=30)
                err = open(err_path).read()
                return _row("sigterm_mid_serve", "child reaches SERVING_READY",
                            f"never ready in 300s; stderr: {err[-200:]}", False)
            _time.sleep(0.25)        # a few ticks: requests genuinely in flight
            p.send_signal(_signal.SIGTERM)
            out, _ = p.communicate(timeout=420)
        except Exception:
            p.kill()
            raise
    err = open(err_path).read()
    drain = None
    for line in out.splitlines():
        if line.startswith("DRAIN "):
            drain = json.loads(line[len("DRAIN "):])
    if drain is None:
        return _row("sigterm_mid_serve", "drain row emitted",
                    f"rc={p.returncode} no DRAIN line; stderr: {err[-200:]}", False)
    ok = (p.returncode == 143 and drain["rc"] == 143
          and drain["finished"] >= 1 and drain["refused"] >= 1
          and drain["finished"] + drain["refused"] == 8
          and drain["in_flight_after"] == 0 and drain["pool_used_after"] == 0
          and drain["full_budget"])
    return _row("sigterm_mid_serve",
                "in-flight drained (full budget), queued refused, exit 143",
                f"rc={p.returncode} {drain}", ok)


# -- RLHF rollout-loop preemption (graft-rlhf, subprocess) -------------------

# stitched-vs-reference loss envelope (parity with RESHARD_LOSS_RTOL): the
# cohort-aligned config below is observed bit-exact on one host — the rtol
# absorbs cross-platform reduction-order drift only
RLHF_STITCH_LOSS_RTOL = 2e-4

_RLHF_CHILD = textwrap.dedent("""
    import json, os, signal, sys, threading, time
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache; use_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingConfig
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology
    from deepspeed_tpu.runtime.resilience.signals import PreemptionGuard
    from deepspeed_tpu.runtime.rlhf import RolloutConfig, RolloutLoop

    CKPT = sys.argv[1]
    FAULT = os.environ.get("RLHF_FB_FAULT") == "1"
    # cohort-aligned config: slots == train_batch_size, uniform budgets,
    # sync_every=1 and align_cohorts=True — every request's entire decode
    # runs under ONE weight generation, so the cohort the drain banks at
    # SIGTERM equals the uninterrupted run's cohort bit-for-bit
    B, TOTAL, PROMPT, NEW = 4, 16, 8, 16

    cfg = get_gpt2_config("test", n_layer=2, n_positions=PROMPT + NEW)

    def loss_fn(logits, batch):
        adv = batch["advantage"]
        mask = batch["mask"].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logp, batch["rollouts"][:, 1:, None],
                                  axis=-1)[..., 0]
        return -(adv[:, None] * tgt * mask[:, 1:]).sum() / jnp.maximum(
            mask[:, 1:].sum(), 1.0)

    ds = {{"train_batch_size": B,
           "optimizer": {{"type": "AdamW", "params": {{"lr": 1e-4}}}},
           "zero_optimization": {{"stage": 3,
                                  "stage3_param_persistence_threshold": 0}},
           "hybrid_engine": {{"enabled": True, "max_out_tokens": PROMPT + NEW,
                              "inference_tp_size": 1}},
           "steps_per_print": 10**9}}
    # pin to ONE device regardless of any inherited
    # --xla_force_host_platform_device_count (pytest's conftest forces 8):
    # train_batch_size=B must stay whole on one data rank, and the
    # checkpoint layout must be identical across every life
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), config=ds, loss_fn=loss_fn,
        topology=MeshTopology(data=1, fsdp=1, devices=jax.devices()[:1]))

    def pad(pairs, adv):
        width = PROMPT + NEW
        toks = np.zeros((len(pairs), width), np.int32)
        mask = np.zeros((len(pairs), width), np.float32)
        for j, (p, o) in enumerate(pairs):
            seq = np.concatenate([np.asarray(p, np.int32),
                                  np.asarray(o, np.int32)])[:width]
            toks[j, :len(seq)] = seq
            mask[j, len(p):len(seq)] = 1.0
        return {{"input_ids": toks, "rollouts": toks, "advantage": adv,
                 "mask": mask}}

    def make_batch(exps):
        pairs = [(np.asarray(e.prompt, np.int32),
                  np.asarray(e.output, np.int32)) for e in exps]
        reward = np.asarray([(np.asarray(o) % 2 == 0).mean()
                             for _, o in pairs], np.float32)
        return pad(pairs, reward - reward.mean())

    def prompt_fn(i):
        r = np.random.RandomState(1234 + i)
        return Request(prompt=r.randint(0, cfg.vocab_size,
                                        size=(PROMPT,)).astype(np.int32),
                       max_new_tokens=NEW)

    engine.initialize_state(pad([(np.zeros(PROMPT, np.int32),
                                  np.zeros(0, np.int32))] * B,
                                np.zeros(B, np.float32)))
    tag, client_state = engine.resume(CKPT)
    guard = PreemptionGuard().install()
    loop = RolloutLoop(engine, prompt_fn, make_batch,
                       RolloutConfig(train_batch_size=B, total_rollouts=TOTAL,
                                     sync_every=1, checkpoint_dir=CKPT,
                                     align_cohorts=True),
                       serving_config=ServingConfig(slots=B,
                                                    prefill_chunk=PROMPT))
    resumed = loop.restore(client_state)
    if FAULT:
        def _arm():
            # a REAL SIGTERM through the flag-only handler, delivered once
            # the learner has stepped so the stitch spans a train/sync
            # boundary (deterministic landing; the external-delivery path
            # is already proven by sigterm_mid_serve)
            while engine.global_steps < 1:
                time.sleep(0.002)
            os.kill(os.getpid(), signal.SIGTERM)
        threading.Thread(target=_arm, daemon=True).start()
    print("RLHF_READY", flush=True)
    res = loop.run(guard=guard, max_ticks=10**6)
    sync = (res["sync_evidence"] or [{{}}])[-1]
    print("RLHF_EXIT " + json.dumps({{
        "rc": res["exit_code"], "learner_steps": res["learner_steps"],
        "consumed": res["experience_consumed"],
        "banked": res["experience_banked"], "dropped": res["dropped"],
        "drained": res.get("drained", 0),
        "refused": res.get("refused_queued", 0),
        "checkpoint_tag": res.get("checkpoint_tag"), "resumed": resumed,
        "resumed_tag": tag, "sync_generation": res["weight_sync_generation"],
        "gather_bytes": sync.get("gather_bytes"),
        "digest_verified": bool(sync.get("digest")),
        "losses": {{str(r["step"]): float(r["loss"]).hex()
                    for r in res["losses"]}}}}), flush=True)
    sys.exit(res["exit_code"])
""")


def _rlhf_life(workdir, ckpt, fault, name):
    """One child life of the rollout loop; returns (rc, RLHF_EXIT row, stderr)."""
    from envutil import cpu_subprocess_env
    env = cpu_subprocess_env()
    env["RLHF_FB_FAULT"] = "1" if fault else "0"
    err_path = os.path.join(workdir, f"rlhf_{name}.stderr")
    with open(err_path, "w") as err_fh:
        p = subprocess.run([PY, "-c", _RLHF_CHILD.format(repo=REPO), ckpt],
                           env=env, stdout=subprocess.PIPE, stderr=err_fh,
                           text=True, cwd=REPO, timeout=600)
    row = None
    for line in p.stdout.splitlines():
        if line.startswith("RLHF_EXIT "):
            row = json.loads(line[len("RLHF_EXIT "):])
    return p.returncode, row, open(err_path).read()


def scenario_rlhf_sigterm(workdir):
    """SIGTERM mid rollout loop (graft-rlhf): in-flight rollouts must drain
    through the PR-14 path (zero dropped — every one banked as experience),
    the learner checkpoints at one step boundary with the loop cursors in
    client_state, and a resumed life finishes the run with a stitched loss
    curve inside RLHF_STITCH_LOSS_RTOL of an uninterrupted reference."""
    total_steps = 4                      # TOTAL // B in the child
    ckpt = os.path.join(workdir, "rlhf_ckpt")
    rc1, life1, err1 = _rlhf_life(workdir, ckpt, fault=True, name="life1")
    if rc1 != 143 or life1 is None:
        return _row("rlhf_sigterm", "life 1 drains and exits 143",
                    f"rc={rc1} row={life1} stderr: {err1[-200:]}", False)
    rc2, life2, err2 = _rlhf_life(workdir, ckpt, fault=False, name="life2")
    if rc2 != 0 or life2 is None:
        return _row("rlhf_sigterm", "life 2 resumes and finishes",
                    f"rc={rc2} row={life2} stderr: {err2[-200:]}", False)
    rc3, ref, err3 = _rlhf_life(workdir, os.path.join(workdir, "rlhf_ref"),
                                fault=False, name="ref")
    if rc3 != 0 or ref is None:
        return _row("rlhf_sigterm", "uninterrupted reference finishes",
                    f"rc={rc3} row={ref} stderr: {err3[-200:]}", False)
    stitched = dict(life1["losses"])
    stitched.update(life2["losses"])
    worst = float("inf")
    bit_exact = False
    if stitched.keys() == ref["losses"].keys():
        worst, bit_exact = 0.0, True
        for k, ref_hex in ref["losses"].items():
            a, b = float.fromhex(stitched[k]), float.fromhex(ref_hex)
            bit_exact = bit_exact and a == b
            worst = max(worst, abs(a - b) / max(abs(b), 1e-12))
    # life 2's learner_steps is the CUMULATIVE cursor (restored at resume),
    # so it must land exactly on the target; its losses list holds only the
    # steps trained this life and must be disjoint from life 1's
    ok = (life1["dropped"] == 0
          and 1 <= life1["learner_steps"] < total_steps
          and life1["checkpoint_tag"] and life2["resumed"]
          and life2["learner_steps"] == total_steps
          and not set(life1["losses"]) & set(life2["losses"])
          and life1["gather_bytes"] is not None and life1["digest_verified"]
          and worst <= RLHF_STITCH_LOSS_RTOL)
    return _row("rlhf_sigterm",
                "drain zero dropped, exit 143, resumed learner stitches the "
                f"loss curve within rtol {RLHF_STITCH_LOSS_RTOL}",
                f"rc={rc1} steps={life1['learner_steps']}+"
                f"{life2['learner_steps']} dropped={life1['dropped']} "
                f"drained={life1['drained']} refused={life1['refused']} "
                f"banked={life1['banked']} worst_rel={worst:.2e} "
                f"bit_exact={bit_exact}", ok,
                checkpoint_tag=life1["checkpoint_tag"],
                sync_generation=life2["sync_generation"],
                gather_bytes=life1["gather_bytes"])


# -- fleet migration scenarios (graft-fleet, in-process) ---------------------
#
# Deliberately LocalReplica-based: the SIGTERM/SIGKILL paths these assert
# are method calls replaying exactly what fleet/worker.py does on the real
# signals, so the migration/readmission *contracts* are provable with one
# shared engine and zero subprocess compile windows. The real-pipes twin
# lives in tests/unit/inference/test_fleet.py under @pytest.mark.slow.

_FLEET_FIXTURE = None


def _fleet_fixture(n_prompts=6, max_new=12):
    """One tiny inference engine shared by every scheduler (compiled
    programs paid once per process), plus the uninterrupted single-replica
    reference outputs that migration parity is asserted against."""
    global _FLEET_FIXTURE
    if _FLEET_FIXTURE is not None:
        return _FLEET_FIXTURE
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 Request, ServingConfig)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test", n_positions=128, dtype=None)
    engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg),
                                          replace_with_kernel_inject=True,
                                          max_out_tokens=128)

    def mk_sched():
        return ContinuousBatchingScheduler(
            engine, ServingConfig(slots=4, prefill_chunk=16, kv_quant=True))

    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)
               for _ in range(n_prompts)]
    ref_sched = mk_sched()
    refs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    for r in refs:
        ref_sched.submit(r)
    ref_sched.run_until_drained()
    ref_ttft_p99 = ref_sched.signals()["ttft_p99"]
    _FLEET_FIXTURE = (mk_sched, prompts, [list(r.output) for r in refs],
                      max_new, ref_ttft_p99)
    return _FLEET_FIXTURE


def _fleet_pair(mk_sched):
    from deepspeed_tpu.inference.fleet import FleetRouter, LocalReplica
    router = FleetRouter()
    replicas = {n: LocalReplica(n, mk_sched()) for n in ("r0", "r1")}
    for n, r in replicas.items():
        router.add_replica(n, r)
    return router, replicas


def scenario_replica_sigterm_migrate(workdir):
    """SIGTERM one of two fleet replicas mid-flight: every in-flight
    request's KV must migrate through a digest-verified bundle to the
    peer (capacity overflow re-dispatched, never dropped) and every
    output must be bit-identical to an uninterrupted run."""
    from deepspeed_tpu.runtime.resilience.manifest import (
        CheckpointCorruptError, verify_checkpoint_dir)
    mk_sched, prompts, ref_out, max_new, _ = _fleet_fixture()
    router, replicas = _fleet_pair(mk_sched)
    rids = [router.submit(p, max_new) for p in prompts]
    for _ in range(6):          # genuinely in flight on both replicas
        router.step()
    victim = replicas["r0"]
    inflight_before = len(victim.scheduler.in_flight)
    bundle = os.path.join(workdir, "fleet_sigterm.bundle")
    victim.sigterm(bundle)
    router.run_until_complete(max_rounds=5000)
    st = router.stats()
    try:                         # the published bundle is manifest-verified
        verify_checkpoint_dir(bundle)
        digest = "verified"
    except (CheckpointCorruptError, FileNotFoundError) as e:
        digest = f"corrupt: {str(e)[:80]}"
    parity = all(router.completed[rid]["output"] == ref_out[i]
                 for i, rid in enumerate(rids) if rid in router.completed)
    ok = (st["completed"] == len(prompts) and st["pending"] == 0
          and st["failed"] == 0 and st["duplicate_completions"] == 0
          and inflight_before >= 1 and digest == "verified" and parity)
    return _row("replica_sigterm_migrate",
                "in-flight KV migrated (digest-verified), zero dropped, "
                "greedy parity with uninterrupted run",
                f"{st} in_flight_at_sigterm={inflight_before} "
                f"bundle={digest} parity={parity}", ok,
                migrated=inflight_before)


def scenario_replica_sigterm_shared_prefix(workdir):
    """SIGTERM a replica whose in-flight requests HOLD shared prefix
    blocks (graft-prefix-cache): ref-counted sharing must not leak into
    the bundle — the export materializes each slot's KV rows (bytes, not
    block refs), the bundle digest verifies, and the peer, whose pool
    shares no state with the victim's, continues every request
    bit-identically to an uninterrupted run."""
    import numpy as np
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.runtime.resilience.manifest import (
        CheckpointCorruptError, verify_checkpoint_dir)
    mk_sched, prompts, _, max_new, _ = _fleet_fixture()
    rng = np.random.default_rng(29)
    template = prompts[0]  # 24 tokens: one full 16-token block shared
    pool_ids = np.concatenate(prompts)
    shared = [np.concatenate([template, rng.choice(pool_ids, 6)])
              .astype(np.int32) for _ in range(6)]
    ref_sched = mk_sched()
    refs = [Request(prompt=p, max_new_tokens=max_new) for p in shared]
    for r in refs:
        ref_sched.submit(r)
    ref_sched.run_until_drained()
    ref_out = [list(r.output) for r in refs]

    router, replicas = _fleet_pair(mk_sched)
    # warm: two requests publish the template's blocks, then retire
    warm_rids = [router.submit(p, max_new) for p in shared[:2]]
    router.run_until_complete(max_rounds=5000)
    # the burst admits against the warm index: prefix affinity routes it
    # to the replica already holding the template's KV
    rids = warm_rids + [router.submit(p, max_new) for p in shared[2:]]
    for _ in range(3):           # genuinely in flight, prefixes restored
        router.step()
    victim = max(replicas.values(), key=lambda r: len(r.scheduler.in_flight))
    shared_held = sum(1 for r in victim.scheduler.in_flight
                      if r.cached_prefix_tokens > 0)
    bundle = os.path.join(workdir, "fleet_sigterm_prefix.bundle")
    victim.sigterm(bundle)
    router.run_until_complete(max_rounds=5000)
    st = router.stats()
    try:
        verify_checkpoint_dir(bundle)
        digest = "verified"
    except (CheckpointCorruptError, FileNotFoundError) as e:
        digest = f"corrupt: {str(e)[:80]}"
    parity = all(router.completed[rid]["output"] == ref_out[i]
                 for i, rid in enumerate(rids) if rid in router.completed)
    ok = (st["completed"] == len(shared) and st["pending"] == 0
          and st["failed"] == 0 and shared_held >= 1
          and digest == "verified" and parity)
    return _row("replica_sigterm_shared_prefix",
                "in-flight requests holding SHARED prefix-cache blocks "
                "migrate digest-verified with greedy parity, zero dropped",
                f"{st} shared_held_at_sigterm={shared_held} "
                f"bundle={digest} parity={parity}", ok,
                migrated=shared_held)


def scenario_replica_sigkill_readmit(workdir):
    """SIGKILL a fleet replica mid-flight: no drain, no bundle — the
    router's liveness sweep must re-admit every orphaned request on the
    peer with at-most-once delivery (duplicates counted, never
    double-delivered), zero dropped, and a bounded TTFT spike."""
    mk_sched, prompts, ref_out, max_new, ref_p99 = _fleet_fixture()
    router, replicas = _fleet_pair(mk_sched)
    rids = [router.submit(p, max_new) for p in prompts]
    for _ in range(4):
        router.step()
    victim = next((r for r in replicas.values()
                   if len(r.scheduler.in_flight)),
                  replicas["r0"])
    victim.sigkill()
    router.run_until_complete(max_rounds=5000)
    st = router.stats()
    parity = all(router.completed[rid]["output"] == ref_out[i]
                 for i, rid in enumerate(rids) if rid in router.completed)
    ttfts = [router.completed[rid]["stats"].get("ttft")
             for rid in router.completed]
    ttft_max = max((t for t in ttfts if t is not None), default=None)
    # re-admitted requests re-run from the prompt, so their TTFT absorbs
    # the time lost to the kill — the spike must stay bounded (a scenario
    # that takes seconds end-to-end, not an unbounded wait), not zero
    ttft_bounded = ttft_max is not None and ttft_max < 30.0
    ok = (st["completed"] == len(prompts) and st["pending"] == 0
          and st["failed"] == 0 and st["readmitted"] >= 1
          and parity and ttft_bounded)
    return _row("replica_sigkill_readmit",
                "orphaned requests re-admitted at-most-once, zero dropped, "
                "bounded TTFT spike, greedy parity",
                f"{st} parity={parity} ttft_max={ttft_max} "
                f"ref_ttft_p99={ref_p99}", ok,
                readmitted=st["readmitted"],
                duplicates=st["duplicate_completions"])


SCENARIOS = {
    "torn_save": scenario_torn_save,
    "serve_drain": scenario_serve_drain,
    "rlhf_sigterm": scenario_rlhf_sigterm,
    "replica_sigterm_migrate": scenario_replica_sigterm_migrate,
    "replica_sigterm_shared_prefix": scenario_replica_sigterm_shared_prefix,
    "replica_sigkill_readmit": scenario_replica_sigkill_readmit,
    "truncate": lambda wd: scenario_corrupt_checkpoint(wd, "truncate"),
    "bitflip": lambda wd: scenario_corrupt_checkpoint(wd, "bitflip"),
    "all_corrupt": scenario_all_corrupt,
    "nan_grads": scenario_overflow_abort,
    "sigkill_resume": scenario_sigkill_resume,
    "http500": scenario_http500_retry,
    "scale_up": scenario_scale_up,
    "scale_down": scenario_scale_down,
}


def main():
    from envutil import pin_cpu_in_process
    pin_cpu_in_process(1)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache
    use_compile_cache()
    want = [s for s in os.environ.get("FAULT_SCENARIOS",
                                      ",".join(SCENARIOS)).split(",") if s]
    workdir = tempfile.mkdtemp(prefix="fault_bench.")
    print(f"# fault bench: {want} (workdir {workdir})", flush=True)
    failed = 0
    try:
        for name in want:
            try:
                row = SCENARIOS[name](workdir)
            except Exception as e:  # noqa: BLE001 — a crashed scenario is a failed contract
                row = _row(name, "scenario completes", f"crashed: {type(e).__name__}: "
                           f"{str(e)[:200]}", False)
            failed += 0 if row["ok"] else 1
            print(json.dumps(row), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"# DONE ok={len(want) - failed}/{len(want)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
