"""Per-rank worker payloads for the multi-process harness (common.py).

Invoked as ``python _worker.py <payload> <json-kwargs>`` in an env prepared
by ``launch_procs`` (CPU-pinned, N virtual devices, DSTPU_* coordinator
vars when multi-process). Each payload prints ONE JSON line.

Payloads mirror the reference's multi-process unit coverage
(``tests/unit/common.py``-launched tests): a ZeRO-3 train step whose loss
must match single-process execution, an orbax save that a different
process topology restores, and per-process (host-local) data feeding.
"""
import json
import os
import struct
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..", "..")))

import jax

import numpy as np


def _bootstrap():
    import deepspeed_tpu

    deepspeed_tpu.comm.init_distributed()  # no-op when DSTPU_* env absent
    return deepspeed_tpu


def _f32_bits(x) -> str:
    return struct.pack(">f", np.float32(x)).hex()


def _build_engine(ds_overrides=None, seq=32, global_bs=8):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test", n_positions=seq, remat=False,
                          attention_backend="xla", dtype=jnp.float32,
                          param_dtype=jnp.float32)
    ds = {
        "train_batch_size": global_bs,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9,
    }
    ds.update(ds_overrides or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg),
                                               config=ds)
    return engine, cfg


def _local_batch(cfg, rank, world, seq=32, global_bs=8, step=0):
    """Every rank derives the SAME global batch from the seed, then feeds
    only its contiguous host-local slice — the per-process data model
    (reference: each rank's loader yields its own shard)."""
    rng = np.random.default_rng(1234 + step)
    ids = rng.integers(0, cfg.vocab_size, (global_bs, seq)).astype(np.int32)
    per = global_bs // world
    return {"input_ids": ids[rank * per:(rank + 1) * per]}


def _global_param_norms(engine):
    """Replicated global param L2^2 and sum — identical on every rank by
    construction (computed in-graph over the sharded tree)."""
    import jax.numpy as jnp

    def _norms(params):
        leaves = jax.tree.leaves(params)
        sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
        s = sum(jnp.sum(l.astype(jnp.float32)) for l in leaves)
        return sq, s

    sq, s = jax.jit(_norms)(engine.state.params)
    return _f32_bits(jax.device_get(sq)), _f32_bits(jax.device_get(s))


def payload_zero3_train(steps=3, save_dir=None, ds_overrides=None):
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    engine, cfg = _build_engine(ds_overrides=ds_overrides)
    engine.initialize_state(_local_batch(cfg, rank, world))
    losses = []
    for step in range(int(steps)):
        loss = engine.train_batch(_local_batch(cfg, rank, world, step=step))
        losses.append(_f32_bits(jax.device_get(loss)))
    sq, s = _global_param_norms(engine)
    out = {"rank": rank, "world": world, "ndev": jax.device_count(),
           "losses": losses, "param_sq": sq, "param_sum": s,
           "global_steps": engine.global_steps}
    if save_dir:
        engine.save_checkpoint(save_dir, tag="mp_tag")
        ds.comm.barrier()
    print(json.dumps(out), flush=True)


def payload_zero3_nvme(steps=2, nvme_path=None):
    """ZeRO-Infinity nvme param offload under real multi-process execution:
    each process journals only its host-local shards into its own swap dir
    (engine appends ``params_proc<i>``) — the reference's per-rank swapper
    model (``partitioned_param_swapper.py:403``)."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    overrides = {"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0,
        "offload_param": {"device": "nvme", "nvme_path": nvme_path,
                          "max_in_cpu": 50000}}}
    engine, cfg = _build_engine(ds_overrides=overrides)
    engine.initialize_state(_local_batch(cfg, rank, world))
    losses = []
    for step in range(int(steps)):
        loss = engine.train_batch(_local_batch(cfg, rank, world, step=step))
        losses.append(_f32_bits(jax.device_get(loss)))
    released = engine.state.params is None
    engine._ensure_params_resident()
    sq, s = _global_param_norms(engine)
    swap_dir = os.path.join(nvme_path, f"params_proc{rank}" if world > 1 else "params")
    n_files = len(os.listdir(swap_dir)) if os.path.isdir(swap_dir) else 0
    print(json.dumps({"rank": rank, "world": world, "losses": losses,
                      "param_sq": sq, "param_sum": s,
                      "released_between_steps": released,
                      "swap_dir": swap_dir, "n_swap_files": n_files}),
          flush=True)


def payload_zero3_infinity(steps=2, nvme_path=None, persistence_threshold=0):
    """The full ZeRO-Infinity recipe under real multi-process execution:
    stage 3 + offload_param (cpu tier) + offload_optimizer (host C++ Adam
    at SHARD granularity — each process steps only the masters of its
    unique addressable shards, engine._offload_step_sharded).
    ``persistence_threshold=None`` keeps the config default (small params
    stay replicated while their grads would default to fsdp — the layout
    split engine._build_step_fns' shard-mode branch must reconcile)."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    zero = {"stage": 3,
            "offload_param": {"device": "cpu"},
            "offload_optimizer": {"device": "cpu"}}
    if persistence_threshold is not None:
        zero["stage3_param_persistence_threshold"] = persistence_threshold
    overrides = {"zero_optimization": zero}
    engine, cfg = _build_engine(ds_overrides=overrides)
    engine.initialize_state(_local_batch(cfg, rank, world))
    losses = []
    for step in range(int(steps)):
        loss = engine.train_batch(_local_batch(cfg, rank, world, step=step))
        losses.append(_f32_bits(jax.device_get(loss)))
    sq, s = _global_param_norms(engine)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(engine.state.params))
    master_elems = sum(int(m.size) for m in engine._host_masters)
    print(json.dumps({"rank": rank, "world": world, "losses": losses,
                      "param_sq": sq, "param_sum": s, "n_params": n_params,
                      "master_elems": master_elems,
                      "shard_mode": bool(getattr(engine, "_host_shard_mode",
                                                 False))}), flush=True)


def payload_restore_check(load_dir=None, steps=1):
    """Restore the 2-process run's checkpoint in THIS topology (typically
    single-process), verify the params match the saver's global norms, then
    train on to prove the restored state is usable."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    engine, cfg = _build_engine()
    engine.initialize_state(_local_batch(cfg, rank, world))
    engine.load_checkpoint(load_dir, tag="mp_tag")
    sq, s = _global_param_norms(engine)
    losses = []
    for step in range(int(steps)):
        loss = engine.train_batch(_local_batch(cfg, rank, world, step=100 + step))
        losses.append(_f32_bits(jax.device_get(loss)))
    print(json.dumps({"rank": rank, "world": world, "param_sq": sq,
                      "param_sum": s, "global_steps": engine.global_steps,
                      "post_losses": losses}), flush=True)


def payload_comm_surface():
    """The process-level comm API on a real 2-process job: ranks, world,
    barrier, and a cross-process collective through the public comm ops."""
    ds = _bootstrap()
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental import multihost_utils
    from jax.experimental.shard_map import shard_map

    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    ds.comm.barrier()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    local = np.full((jax.local_device_count(),), float(rank + 1), np.float32)
    glob = multihost_utils.host_local_array_to_global_array(local, mesh, P("data"))
    f = shard_map(lambda x: ds.comm.all_reduce(x, group="data"),
                  mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    with mesh:
        out = jax.jit(f)(glob)
    # SUM over 8 shards: 4 shards of 1.0 (rank 0) + 4 of 2.0 (rank 1) = 12
    val = float(jax.device_get(multihost_utils.process_allgather(out, tiled=True))[0])
    print(json.dumps({"rank": rank, "world": world,
                      "ndev": jax.device_count(),
                      "local_ndev": jax.local_device_count(),
                      "allreduce": val}), flush=True)


def payload_scaling_compile(model="125m", seq=256, mb=1):
    """Compile (not run) the ZeRO-3 train step over the global mesh and
    report per-chip collective payload bytes from the SPMD HLO — the
    multi-PROCESS version of tools/scaling_report.py's strategy check.
    Realistic model scale on purpose: GSPMD strategy bugs (batch
    replication, backward all-gathers) do not reproduce on toy models
    (r3 finding, perf-measurement-rules)."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "..", ".."))
    from unit.runtime.test_qcomm import collective_payload_bytes

    n = jax.device_count()
    cfg = get_gpt2_config(model, n_positions=seq, vocab_size=50304,
                          dtype=jnp.bfloat16)
    topo = MeshTopology(fsdp=n)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), topology=topo,
        config={"train_batch_size": int(mb) * n,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3,
                                      "stage3_param_persistence_threshold": 0}})
    local_rows = int(mb) * n // world
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size,
                                       (local_rows, seq)).astype(np.int32)}
    engine.initialize_state(batch)
    hlo = engine.lower_train_step(batch).compile().as_text()
    import re
    per_op = {}
    pat = re.compile(r"= ((?:\([^)]*\)|\S+)) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)\(")
    shp = re.compile(r"(bf16|f16|f32|s32|u32|s8|u8)\[([0-9,]*)\]")
    bytes_of = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m:
            continue
        nb = 0
        for dt, dims in shp.findall(m.group(1)):
            k = 1
            for d in dims.split(","):
                if d:
                    k *= int(d)
            nb += k * bytes_of[dt]
        per_op[m.group(2)] = per_op.get(m.group(2), 0) + nb
    print(json.dumps({"rank": rank, "world": world, "ndev": n,
                      "payload_bytes": collective_payload_bytes(hlo),
                      "per_op": per_op}), flush=True)


def payload_pipe_train(steps=2):
    """Pipeline engine with the PIPE AXIS SPANNING PROCESSES: every
    activation hop (lax.ppermute) and tied-grad psum crosses the process
    boundary over gloo — the multi-node pipeline the reference runs over
    NCCL p2p (pipe/engine.py:795)."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    import deepspeed_tpu
    from deepspeed_tpu.models import get_gpt2_config
    from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
    from deepspeed_tpu.parallel.topology import MeshTopology
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    n = jax.device_count()
    # mesh device order is process-major, so pipe=2 as the OUTER axis puts
    # stage 0 on process 0 and stage 1 on process 1
    topo = MeshTopology(pipe=2, fsdp=n // 2, devices=jax.devices())
    cfg = get_gpt2_config("test", n_layer=2, n_embd=32, n_head=2,
                          n_positions=32)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    assert topo.pipe_parallel_size == 2
    fsdp = n // 2
    tbs = 4 * fsdp
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=pipe, topology=topo,
        config={"train_batch_size": tbs, "gradient_accumulation_steps": 4,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1},
                "gradient_clipping": 1.0, "steps_per_print": 10**9})
    rng = np.random.default_rng(5)
    losses = []
    for step in range(int(steps)):
        ids = rng.integers(0, cfg.vocab_size, (tbs, 32)).astype(np.int32)
        # the pipe axis is NOT a batch axis: the batch is replicated across
        # pipe stages and sharded over each stage's LOCAL fsdp devices, so
        # every process feeds the FULL global batch (its host-local view of
        # a pipe-replicated array is the whole thing)
        loss = engine.train_batch({"input_ids": ids})
        losses.append(_f32_bits(jax.device_get(loss)))
    print(json.dumps({"rank": rank, "world": world, "losses": losses}),
          flush=True)


def payload_moe_train(steps=2):
    """MoE engine with the EXPERT AXIS SPANNING PROCESSES: the dispatch/
    combine all-to-alls cross the process boundary — the reference's
    inter-node expert parallelism."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    n = jax.device_count()
    topo = MeshTopology(expert=2, fsdp=n // 2, devices=jax.devices())
    cfg = get_gpt2_config("test", n_layer=2, n_embd=32, n_head=2,
                          n_positions=32, moe_num_experts=2, moe_layer_freq=2)
    tbs = 2 * (n // 2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), topology=topo,
        config={"train_batch_size": tbs,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 2},
                "steps_per_print": 10**9})
    rng = np.random.default_rng(6)
    losses = []
    for step in range(int(steps)):
        ids = rng.integers(0, cfg.vocab_size, (tbs, 32)).astype(np.int32)
        local = ids[rank * (tbs // world):(rank + 1) * (tbs // world)] \
            if world > 1 else ids
        loss = engine.train_batch({"input_ids": local})
        losses.append(_f32_bits(jax.device_get(loss)))
    print(json.dumps({"rank": rank, "world": world, "losses": losses}),
          flush=True)


def payload_elastic_train(total_steps=4, ckpt=None, losses_path=None,
                          crash_at=-1):
    """Elastic-recovery training payload: deterministic per-step data,
    checkpoint + heartbeat every step, optional injected crash (one rank
    dying kills the gang — the multi-host failure the agent must convert
    into a restart at the surviving topology)."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    import jax.numpy as jnp

    from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat

    engine, cfg = _build_engine(ds_overrides={"zero_optimization": {"stage": 1}})
    engine.initialize_state(_local_batch(cfg, rank, world))
    engine.load_checkpoint(ckpt)  # no-op on the first launch
    while engine.global_steps < int(total_steps):
        step = engine.global_steps
        loss = float(jnp.asarray(engine.train_batch(
            _local_batch(cfg, rank, world, step=step))))
        if rank == 0 and losses_path:
            with open(losses_path, "a") as f:
                f.write(json.dumps({"step": step, "world_procs": world,
                                    "loss": loss}) + "\n")
        engine.save_checkpoint(ckpt)
        touch_heartbeat()
        if rank == max(world - 1, 0) and step + 1 == int(crash_at):
            os._exit(1)  # one rank dies -> the gang dies
    print(json.dumps({"rank": rank, "world": world,
                      "global_steps": engine.global_steps}), flush=True)


def payload_data_sampler(total=64, micro=4):
    """Per-process data sharding through the production sampler: each rank's
    index stream must be disjoint and jointly covering."""
    ds = _bootstrap()
    rank, world = ds.comm.get_rank(), ds.comm.get_world_size()
    from deepspeed_tpu.runtime.data_pipeline.data_sampling.data_sampler import (
        DeepSpeedDataSampler)

    sampler = DeepSpeedDataSampler(
        data_efficiency_config={}, one_epoch_total_samples=int(total),
        micro_batch_size=int(micro), data_parallel_rank=rank,
        data_parallel_size=world, gradient_accumulation_steps=1)
    idx = [int(i) for batch in list(iter(sampler))[:4] for i in np.asarray(batch).ravel()]
    print(json.dumps({"rank": rank, "world": world, "indices": idx}), flush=True)


def main():
    payload, kwargs = sys.argv[1], json.loads(sys.argv[2] if len(sys.argv) > 2 else "{}")
    fn = globals().get(f"payload_{payload}")
    if fn is None:
        raise SystemExit(f"unknown payload {payload!r}")
    fn(**kwargs)


if __name__ == "__main__":
    main()
