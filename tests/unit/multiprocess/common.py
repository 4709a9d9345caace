"""Multi-process launch harness — the TPU-native answer to the reference's
process-spawning unit framework (reference ``tests/unit/common.py:147``
``_launch_procs`` + per-rank env setup ``:188-211``).

The reference forks N torch.distributed ranks over NCCL/gloo; here we spawn
N OS processes that bootstrap into ONE jax distributed job over a localhost
coordinator (``deepspeed_tpu.comm.init_distributed`` →
``jax.distributed.initialize``), each owning ``devices_per_proc`` virtual
CPU devices. Cross-process collectives ride gloo; the global mesh spans
every process's devices, exactly like a multi-host TPU pod over DCN.

Workers run payload functions from ``_worker.py`` (name + json kwargs on
argv) and print one JSON result line; :func:`launch_procs` collects one
parsed result per rank. The workers are CPU-pinned and hold no chip, so a
timeout may kill them.
"""
import json
import os
import socket
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_worker.py")


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_procs(payload: str, n_procs: int = 2, devices_per_proc: int = 4,
                 timeout: int = 600, **kwargs):
    """Run ``_worker.py``'s ``payload_<payload>`` in ``n_procs`` processes.

    Returns a list of per-rank result dicts (rank order). Raises with both
    ranks' stderr tails on any failure. ``n_procs=1`` runs the same payload
    single-process (no distributed init) — the parity reference."""
    sys.path.insert(0, REPO)
    from envutil import cpu_subprocess_env

    port = free_port()
    procs = []
    for rank in range(n_procs):
        env = cpu_subprocess_env(n_virtual_devices=devices_per_proc)
        if n_procs > 1:
            env["DSTPU_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
            env["DSTPU_NUM_PROCESSES"] = str(n_procs)
            env["DSTPU_PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, payload, json.dumps(kwargs)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=REPO))
    results, errs = [], []
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:  # CPU-only children hold no chip
                q.kill()
            raise RuntimeError(f"rank {rank} timed out after {timeout}s")
        line = _last_json_line(out)
        if p.returncode != 0 or line is None:
            errs.append(f"rank {rank} rc={p.returncode}:\n{err[-2000:]}")
        else:
            results.append(line)
    if errs:
        raise RuntimeError("multiprocess launch failed:\n" + "\n".join(errs))
    return results


def _last_json_line(text):
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None
