"""Elastic restart supervisor (reference ``elasticity/elastic_agent.py:28``
``DSElasticAgent`` role): a dead or hung training backend is detected, the
job is relaunched at the surviving world size, and training resumes from
the orbax checkpoint with a matching loss continuation.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# The supervised training job: GPT-2 test model, fsdp = DS_ELASTIC_WORLD_SIZE,
# fixed global batch (any ladder size divides it), per-step deterministic
# data, checkpoint + heartbeat every step. Failure injection:
#   CRASH_AT_STEP  — os._exit(1) before that step completes (first launch only)
#   HANG_AT_STEP   — stop heartbeating and sleep (hang simulation)
CHILD = textwrap.dedent("""
    import json, os, sys, time
    world = int(os.environ["DS_ELASTIC_WORLD_SIZE"])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    sys.path.insert(0, __REPO__)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from envutil import use_compile_cache; use_compile_cache()
    import numpy as np, jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    first_launch = os.environ.get("DS_ELASTIC_RESTART_COUNT", "0") == "0"
    crash_at = int(os.environ.get("CRASH_AT_STEP", "-1")) if first_launch else -1
    hang_at = int(os.environ.get("HANG_AT_STEP", "-1")) if first_launch else -1
    ckpt = os.environ["CKPT_DIR"]
    losses_path = os.environ["LOSSES_PATH"]
    total_steps = int(os.environ.get("TOTAL_STEPS", "4"))

    cfg = get_gpt2_config("test", n_layer=2)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), topology=MeshTopology(fsdp=world),
        config={"train_batch_size": 8,
                 "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                 "zero_optimization": {"stage": 1}})
    eng.initialize_state({"input_ids": np.zeros((8, 16), np.int32)})
    eng.load_checkpoint(ckpt)  # no-op on the first launch
    while eng.global_steps < total_steps:
        step = eng.global_steps
        if step == hang_at:
            time.sleep(600)  # hung backend: heartbeat goes silent
        rng = np.random.RandomState(1000 + step)
        batch = {"input_ids": rng.randint(0, cfg.vocab_size, (8, 16)).astype(np.int32)}
        loss = float(jnp.asarray(eng.train_batch(batch)))
        with open(losses_path, "a") as f:
            f.write(json.dumps({"step": step, "world": world, "loss": loss}) + "\\n")
        eng.save_checkpoint(ckpt)
        touch_heartbeat()
        if step + 1 == crash_at:
            os._exit(1)  # simulated worker death mid-job
    print("CHILD_DONE", eng.global_steps)
""").replace("__REPO__", repr(REPO))


def _scrubbed_env(extra):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from envutil import cpu_subprocess_env
    env = cpu_subprocess_env()
    env.pop("XLA_FLAGS", None)  # the child pins its own device count
    env.update(extra)
    return env


def _read_losses(path):
    if not os.path.exists(path):
        return []
    return [json.loads(l) for l in open(path).read().strip().splitlines()]


def _run_agent(tmp_path, fail_env, world_sizes, heartbeat_timeout=90.0):
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    tmp_path.mkdir(parents=True, exist_ok=True)
    child_py = tmp_path / "child.py"
    child_py.write_text(CHILD)
    losses = tmp_path / "losses.jsonl"
    env = _scrubbed_env(dict(fail_env,
                             CKPT_DIR=str(tmp_path / "ckpt"),
                             LOSSES_PATH=str(losses)))
    agent = DSElasticAgent([sys.executable, str(child_py)],
                           world_sizes=world_sizes,
                           heartbeat_timeout=heartbeat_timeout,
                           max_restarts=2, env=env)
    rc = agent.run(workdir=str(tmp_path))
    return rc, agent, _read_losses(losses)


def test_crash_recovery_resumes_at_new_world_size(tmp_path):
    """Worker dies after step 2 at world 8 → agent relaunches at world 4 →
    training resumes from the checkpoint and completes, and the continued
    loss curve matches an uninterrupted run."""
    rc, agent, rows = _run_agent(tmp_path, {"CRASH_AT_STEP": "2"}, [8, 4])
    assert rc == 0, agent.history
    assert agent.restart_count == 1, agent.history
    steps = [(r["step"], r["world"]) for r in rows]
    assert steps == [(0, 8), (1, 8), (2, 4), (3, 4)], steps

    # uninterrupted reference at a FIXED world size: the continued curve
    # must match within cross-world reduction-order tolerance
    ref_rc, _, ref_rows = _run_agent(tmp_path / "ref", {}, [8])
    assert ref_rc == 0
    for got, want in zip(rows, ref_rows):
        assert got["step"] == want["step"]
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=2e-4), (got, want)


def test_hang_detection_kills_and_restarts(tmp_path):
    """Heartbeat silence (the hang signature) is a failure: the hung child
    is killed and the job restarts at the next world size and completes."""
    # the silence waited out is a timer: it has only to outlast a step and
    # its checkpoint (the first compile falls under the start-up budget)
    rc, agent, rows = _run_agent(tmp_path, {"HANG_AT_STEP": "1"}, [4, 2],
                                 heartbeat_timeout=20.0)
    assert rc == 0, agent.history
    assert agent.restart_count == 1
    assert "heartbeat silent" in agent.history[0]["reason"], agent.history
    worlds = {r["step"]: r["world"] for r in rows}
    assert worlds[0] == 4 and worlds[3] == 2, rows


def test_agent_history_records_topology_transitions(tmp_path):
    """With a checkpoint_dir, every attempt's history row carries the
    old→new topology record (from metadata stamps alone — the supervisor
    never opens checkpoint state): first attempt fresh, restart at a
    different world decided as reshard against the stamped world size."""
    import json as _json

    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent

    ckpt = tmp_path / "ckpt"
    fail_flag = tmp_path / "fail_once"
    fail_flag.write_text("")
    # child: fails once (forcing a restart at the next world), then fakes a
    # checkpoint publish stamped at world 4 and exits 0 — no jax involved
    child = (
        "import json, os, sys\n"
        f"flag = {str(fail_flag)!r}\n"
        f"ckpt = {str(ckpt)!r}\n"
        "tag = os.path.join(ckpt, 'global_step1')\n"
        "os.makedirs(tag, exist_ok=True)\n"
        "open(os.path.join(tag, 'state'), 'w').write('x')\n"
        "json.dump({'global_steps': 1, 'world_size': 4,\n"
        "           'mesh_axes': {'data': 1, 'fsdp': 4}},\n"
        "          open(os.path.join(tag, 'metadata.json'), 'w'))\n"
        "open(os.path.join(ckpt, 'latest'), 'w').write('global_step1')\n"
        "if os.path.exists(flag):\n"
        "    os.unlink(flag)\n"
        "    sys.exit(1)\n"
    )
    agent = DSElasticAgent([sys.executable, "-c", child], world_sizes=[4, 8],
                           max_restarts=2, checkpoint_dir=str(ckpt))
    rc = agent.run(workdir=str(tmp_path))
    assert rc == 0 and agent.restart_count == 1, agent.history
    first, second = agent.history
    # attempt 1 found no checkpoint yet -> fresh, no previous world
    assert first["topology"]["resume"] == "fresh"
    assert first["topology"]["prev_world_size"] is None
    # attempt 2 found the world-4 stamp and targets world 8 -> reshard
    topo = second["topology"]
    assert topo["resume"] == "reshard" and topo["ckpt_world"] == 4
    assert topo["world_size"] == 8 and topo["prev_world_size"] == 4
    assert topo["tag"] == "global_step1"
    assert _json.dumps(agent.history)  # rows stay JSON-serializable


def test_decide_resume_reads_stamps_only(tmp_path):
    """decide_resume: fresh on empty, plain on matching topology, reshard
    on axis-split change even at equal world size, unknown on pre-stamp
    metadata."""
    import json as _json

    from deepspeed_tpu.runtime.elastic.agent import decide_resume

    ckpt = tmp_path / "ck"
    assert decide_resume(str(ckpt), 4)["resume"] == "fresh"
    tag = ckpt / "t1"
    tag.mkdir(parents=True)
    (tag / "state").write_text("x")
    meta = {"global_steps": 3, "world_size": 4, "mesh_axes": {"data": 2, "fsdp": 2}}
    (tag / "metadata.json").write_text(_json.dumps(meta))
    assert decide_resume(str(ckpt), 4)["resume"] == "plain"
    assert decide_resume(str(ckpt), 2)["resume"] == "reshard"
    # same world, different split: still a reshard when axes are known
    d = decide_resume(str(ckpt), 4, target_axes={"data": 1, "fsdp": 4})
    assert d["resume"] == "reshard" and d["ckpt_axes"] == {"data": 2, "fsdp": 2}
    # pre-elastic tag (no stamp): unknown — the restore will be unplanned
    (tag / "metadata.json").write_text(_json.dumps({"global_steps": 3}))
    assert decide_resume(str(ckpt), 4)["resume"] == "unknown"


def test_validate_world_sizes_rejects_invalid_ladder():
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    ds = {"elasticity": {"enabled": True, "max_train_batch_size": 8,
                         "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 4,
                         "version": 0.1},
          "train_batch_size": 8}
    agent = DSElasticAgent(["true"], world_sizes=[4, 3])
    with pytest.raises(Exception):
        agent.validate_world_sizes(ds)  # 3 gpus can't hit batch 8 with mb 2/4
    DSElasticAgent(["true"], world_sizes=[4, 2, 1]).validate_world_sizes(ds)
