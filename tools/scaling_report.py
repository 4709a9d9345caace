"""ZeRO-3 weak-scaling report over virtual meshes, 8 → 256 chips.

BASELINE.md's primary metric includes "ZeRO-3 scaling efficiency 8→256
chips (GPT-2-XL)". Real multi-chip hardware is not available here, but the
thing that decides weak-scaling efficiency — what each chip must move over
ICI per step — IS checkable without chips: compile the ZeRO-3 train step
for N virtual CPU devices and read the collective payload bytes out of the
SPMD-partitioned HLO. Weak scaling holds when per-chip payload stays ~flat
as N grows (each chip always gathers the full parameter set and
reduce-scatters the full gradient set, independent of N — the reference's
ZeRO-3 has the same invariant, ``stage3.py:1176`` reduce_scatter over the
whole DP group).

Each N runs in a fresh subprocess (device count is fixed at jax import);
the parent prints one JSON line per N plus a verdict. Pure-CPU work: no
chip is touched.

Run: python tools/scaling_report.py          [MODEL=125m SEQ=128 MB_PER_CHIP=1]
     Default meshes 8,16,64,256. MESHES=8,64,512 reaches 512 virtual
     chips — supported, but XLA's 512-partition CPU compile of the 125m
     step runs >30 min on a 14-core host (use MODEL=test SEQ=64 for a
     tractable 512-way check; the invariant is scale-free).
"""
import json
import os
import subprocess
import sys

_DEFAULT_MESHES = "8,16,64" if int(os.environ.get("MOE", "0")) else "8,16,64,256"
# MoE default stops at 64: the [G,S,E] gating-mask payload is inherent and
# ~linear in total experts (E = k*N), so past the calibrated 8->64 span the
# verdict would flag healthy plans; override MESHES to look further.
MESHES = [int(n) for n in os.environ.get("MESHES", _DEFAULT_MESHES).split(",")]
MODEL = os.environ.get("MODEL", "125m")
SEQ = int(os.environ.get("SEQ", "128"))
MB_PER_CHIP = int(os.environ.get("MB_PER_CHIP", "1"))
# lane-aligned AND 256-divisible vocab so the fsdp axis always divides
VOCAB = int(os.environ.get("VOCAB", "50432"))
# TP=k carves a fixed tensor axis out of each mesh (the LLaMA + ZeRO++
# ladder shape: fsdp grows, tensor stays constant); per-chip payload must
# still stay flat as the fsdp factor grows
TP = int(os.environ.get("TP", "1"))
# MOE=k switches to expert-parallel weak scaling (the GPT-MoE ladder
# rung): the mesh axis is `expert` instead of `fsdp`, with k local
# experts per chip (total experts = k * N). Flatness here means the a2a
# dispatch + replicated-dense allreduce per chip don't grow with N.
MOE = int(os.environ.get("MOE", "0"))
# OFFLOAD=1 switches the fsdp sweep to the ZeRO-Infinity step (stage 3 +
# offload_param cpu): params rest host-side and stream per layer — the
# per-chip ICI payload must stay as flat as the dense stage-3 step's
# (streaming changes WHERE params rest, not what chips exchange)
OFFLOAD = int(os.environ.get("OFFLOAD", "0"))

CHILD = r"""
import os, sys, time
sys.path.insert(0, {repo!r}); sys.path.insert(0, {repo!r} + "/tests")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.parallel.topology import MeshTopology
from unit.runtime.test_qcomm import collective_payload_bytes

n = {n}
tp = {tp}
moe = {moe}
offload = {offload}
t0 = time.time()
extra = dict(moe_num_experts=moe * n, moe_layer_freq=2, moe_k=1) if moe else {{}}
cfg = get_gpt2_config({model!r}, n_positions={seq}, vocab_size={vocab}, **extra)
topo = MeshTopology(expert=n) if moe else MeshTopology(fsdp=n // tp, tensor=tp)
zero_cfg = {{"stage": 1 if moe else 3, "stage3_param_persistence_threshold": 0}}
if offload:
    zero_cfg["offload_param"] = {{"device": "cpu"}}
engine, _, _, _ = deepspeed_tpu.initialize(
    model=GPT2LMHeadModel(cfg), topology=topo,
    config={{"train_batch_size": {mb} * (n if moe else n // tp),
            "optimizer": {{"type": "AdamW", "params": {{"lr": 1e-3}}}},
            "bf16": {{"enabled": True}},
            "zero_optimization": zero_cfg}})
rng = np.random.default_rng(0)
batch = {{"input_ids": rng.integers(0, cfg.vocab_size,
                                    ({mb} * (n if moe else n // tp), {seq})).astype(np.int32)}}
engine.initialize_state(batch)
hlo = engine.lower_train_step(batch).compile().as_text()
print("RESULT", n, collective_payload_bytes(hlo), round(time.time() - t0, 1))
"""


def run_mesh(n):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo) if repo not in sys.path else None
    from envutil import cpu_subprocess_env
    env = cpu_subprocess_env(n_virtual_devices=n)
    code = CHILD.format(repo=repo, n=n, model=MODEL, seq=SEQ, vocab=VOCAB,
                        mb=MB_PER_CHIP, tp=TP, moe=MOE, offload=OFFLOAD)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1800)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            _, n_, payload, secs = line.split()
            return int(payload), float(secs)
    raise RuntimeError(f"mesh {n} failed:\n{r.stderr[-1500:]}")


def main():
    if MOE and TP > 1:
        print(json.dumps({"error": "MOE mode scales the expert axis; combine "
                          "with TP via the config-ladder tests instead"}), flush=True)
        return 2
    if MOE and OFFLOAD:
        print(json.dumps({"error": "MOE mode runs stage 1 (replicated dense + "
                          "expert a2a); offload_param is a stage-3 feature — "
                          "measure them separately"}), flush=True)
        return 2
    results = {}
    for n in MESHES:
        payload, secs = run_mesh(n)
        results[n] = payload
        print(json.dumps({"mesh": n, "tp": TP, "moe": MOE, "offload": OFFLOAD,
                          "per_chip_collective_bytes": payload,
                          "compile_s": secs}), flush=True)
    if len(MESHES) < 2:
        # one mesh measures nothing about scaling — say so, don't pass
        print(json.dumps({"model": MODEL, "weak_scaling_flat": None,
                          "note": "need >=2 mesh sizes to compare"}), flush=True)
        return 2
    base_n = MESHES[0]
    worst = max(results[n] / results[base_n] for n in MESHES[1:])
    # fsdp/TP meshes measure flat at 1.000 (PERF.md r3) — 10% budget total.
    # MoE carries the inherent [G,S,E] gating-mask term (E grows with the
    # mesh): 35% over the calibrated 8->64 span (measured 1.315; the
    # default MoE mesh list stops at 64 for exactly this reason).
    bound = 1.35 if MOE else 1.10
    flat = worst <= bound
    print(json.dumps({"model": MODEL, "weak_scaling_flat": flat, "bound": bound,
                      "max_payload_growth_vs_first": round(worst, 3)}), flush=True)
    return 0 if flat else 1


if __name__ == "__main__":
    sys.exit(main())
