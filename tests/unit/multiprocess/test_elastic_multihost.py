"""Multi-host elastic recovery: a 2-process gang dies mid-training and
``DSElasticAgent`` restarts the job SINGLE-process, resuming from the
orbax checkpoint the 2-process job saved — the reference's
host-loss-then-resume story (torchelastic membership change + DeepSpeed
elastic batch math) composed end to end on real OS processes.

The supervised command is a gang runner: at the ladder's first world it
spawns a 2-process ``jax.distributed`` job (4 virtual devices each); when
the agent restarts after the injected rank death, the next ladder entry
runs the same payload single-process on 8 devices. Both topologies see
the same 8-device global mesh, so the loss continuation must match an
uninterrupted run within cross-process reduction tolerance.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from tests.unit.multiprocess.common import REPO, WORKER, free_port

GANG_RUNNER = textwrap.dedent("""
    import json, os, socket, subprocess, sys, time
    sys.path.insert(0, __REPO__)
    from envutil import cpu_subprocess_env

    world = int(os.environ["DS_ELASTIC_WORLD_SIZE"])  # devices in the mesh
    first = os.environ.get("DS_ELASTIC_RESTART_COUNT", "0") == "0"
    kwargs = {"total_steps": int(os.environ["TOTAL_STEPS"]),
              "ckpt": os.environ["CKPT_DIR"],
              "losses_path": os.environ["LOSSES_PATH"],
              "crash_at": int(os.environ["CRASH_AT_STEP"]) if first else -1}
    n_procs = 2 if world == 8 and first else 1
    per = world // n_procs
    s = socket.socket(); s.bind(("127.0.0.1", 0)); port = s.getsockname()[1]; s.close()
    procs = []
    for rank in range(n_procs):
        env = cpu_subprocess_env(n_virtual_devices=per)
        if n_procs > 1:
            env["DSTPU_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
            env["DSTPU_NUM_PROCESSES"] = str(n_procs)
            env["DSTPU_PROCESS_ID"] = str(rank)
        else:
            for k in ("DSTPU_COORDINATOR_ADDRESS", "DSTPU_NUM_PROCESSES",
                      "DSTPU_PROCESS_ID"):
                env.pop(k, None)
        # the heartbeat file env rides through so every rank's engine
        # touches the agent's liveness signal
        procs.append(subprocess.Popen(
            [sys.executable, __WORKER__, "elastic_train", json.dumps(kwargs)],
            env=env, cwd=__REPO__))
    # a rank that dies takes the gang with it, as under a launcher, after a
    # grace for a checkpoint's last writes: left alone, the survivor waits out
    # the coordination service's own timeout (about 90 s) before it fails
    died = None
    while any(p.poll() is None for p in procs):
        if died is None and any(p.poll() not in (None, 0) for p in procs):
            died = time.time()
        if died is not None and time.time() - died > 10.0:
            for p in procs:
                p.kill()
        time.sleep(0.2)
    sys.exit(0 if all(p.returncode == 0 for p in procs) else 1)
""")


def _read_losses(path):
    if not os.path.exists(path):
        return []
    return [json.loads(l) for l in open(path).read().strip().splitlines()]


@pytest.mark.parametrize("crash_at", [2])
def test_two_process_gang_death_resumes_single_process(tmp_path, crash_at):
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent

    runner = tmp_path / "gang_runner.py"
    runner.write_text(GANG_RUNNER.replace("__REPO__", repr(REPO))
                      .replace("__WORKER__", repr(WORKER)))
    losses = tmp_path / "losses.jsonl"
    env = dict(os.environ,
               TOTAL_STEPS="4", CKPT_DIR=str(tmp_path / "ckpt"),
               LOSSES_PATH=str(losses), CRASH_AT_STEP=str(crash_at))
    agent = DSElasticAgent([sys.executable, str(runner)],
                           world_sizes=[8, 8],  # same mesh, fewer processes
                           heartbeat_timeout=240.0, startup_timeout=240.0,
                           max_restarts=2, env=env)
    rc = agent.run(workdir=str(tmp_path))
    assert rc == 0, agent.history
    assert agent.restart_count == 1, agent.history
    rows = _read_losses(losses)
    steps = [(r["step"], r["world_procs"]) for r in rows]
    # steps 0-1 ran in the 2-process gang; the injected death killed it;
    # steps 2-3 resumed single-process from the 2-process checkpoint
    assert steps == [(0, 2), (1, 2), (2, 1), (3, 1)], steps

    # loss continuation matches an uninterrupted single-process run
    ref_losses = tmp_path / "ref_losses.jsonl"
    env_ref = dict(env, LOSSES_PATH=str(ref_losses), CRASH_AT_STEP="-1",
                   CKPT_DIR=str(tmp_path / "ref_ckpt"))
    # DS_ELASTIC_RESTART_COUNT=1 forces the runner's single-process branch
    p = subprocess.run([sys.executable, str(runner)],
                       env=dict(env_ref, DS_ELASTIC_WORLD_SIZE="8",
                                DS_ELASTIC_RESTART_COUNT="1"),
                       capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-1500:]
    ref_rows = _read_losses(ref_losses)
    assert [r["step"] for r in ref_rows] == [0, 1, 2, 3]
    for got, want in zip(rows, ref_rows):
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=2e-4)
