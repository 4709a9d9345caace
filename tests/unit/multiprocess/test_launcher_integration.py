"""Production-launcher → multi-process bootstrap integration.

The reference's launcher spawns per-device ranks wired into
torch.distributed (``launcher/launch.py:132``); ours spawns one process
per node wired into ``jax.distributed`` via env. This test runs the REAL
``deepspeed_tpu/launcher/launch.py`` twice (node_rank 0 and 1, local
coordinator) around a user script that calls ``comm.init_distributed()``
— pinning the env contract end to end. r5 found (and this test now
guards) a silent integration bug: the launcher exported only JAX_* names
while init_distributed read only DSTPU_* names, so multi-node launches
fell through to N disjoint single-host jobs.
"""
import json
import os
import subprocess
import sys

from tests.unit.multiprocess.common import REPO, _last_json_line, free_port

LAUNCH = os.path.join(REPO, "deepspeed_tpu", "launcher", "launch.py")

USER_SCRIPT = """
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import deepspeed_tpu
deepspeed_tpu.comm.init_distributed()
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental import multihost_utils
rank, world = deepspeed_tpu.comm.get_rank(), deepspeed_tpu.comm.get_world_size()
mesh = Mesh(np.array(jax.devices()), ("data",))
local = np.full((jax.local_device_count(),), float(rank + 1), np.float32)
glob = multihost_utils.host_local_array_to_global_array(local, mesh, P("data"))
with mesh:
    total = jax.jit(lambda x: x.sum())(glob)
print(json.dumps({{"rank": rank, "world": world, "ndev": jax.device_count(),
                  "sum": float(total)}}), flush=True)
"""


def test_launcher_bootstraps_two_node_local_job(tmp_path):
    script = tmp_path / "user_script.py"
    script.write_text(USER_SCRIPT.format(repo=REPO))
    sys.path.insert(0, REPO)
    from envutil import cpu_subprocess_env

    port = free_port()
    procs = []
    for rank in range(2):
        env = cpu_subprocess_env(n_virtual_devices=4)
        # the launcher copies ITS env into the child; DSTPU_*/JAX_* must
        # come from the launcher args, not inherited state
        for k in list(env):
            if k.startswith(("DSTPU_", "JAX_NUM", "JAX_PROCESS")):
                env.pop(k)
        # launch.py imports deepspeed_tpu; source checkout isn't installed
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, LAUNCH, "--node_rank", str(rank),
             "--nnodes", "2", "--master_addr", "127.0.0.1",
             "--master_port", str(port), str(script)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=REPO))
    results = []
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"node {rank}: {err[-1500:]}"
        line = _last_json_line(out)
        assert line is not None, f"node {rank} printed no JSON: {out[-500:]}"
        results.append(line)
    for rank, r in enumerate(results):
        assert r["rank"] == rank
        assert r["world"] == 2, ("launcher-spawned job fell back to "
                                 "single-process (env contract broken)", r)
        assert r["ndev"] == 8
        # 4 shards of 1.0 (node 0) + 4 shards of 2.0 (node 1)
        assert r["sum"] == 12.0
