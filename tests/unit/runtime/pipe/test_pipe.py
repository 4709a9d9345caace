"""Pipeline-parallelism tests (reference ``tests/unit/runtime/pipe/``):
schedule semantics, pipeline-vs-dense numerical parity, end-to-end training."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.models.gpt2 import cross_entropy_loss, gpt2_pipe_layers
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.runtime.pipe import schedule as sched
from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


# ---------------------------------------------------------------------------
# schedule semantics (reference tests/unit/runtime/pipe/test_pipe_schedule.py)
# ---------------------------------------------------------------------------
def test_train_schedule_counts():
    M, S = 6, 3
    for stage in range(S):
        s = sched.TrainSchedule(micro_batches=M, stages=S, stage_id=stage)
        steps = list(s.steps())
        assert len(steps) == 2 * (M + S - 1)
        fwd = sum(1 for cmds in steps for c in cmds if isinstance(c, sched.ForwardPass))
        bwd = sum(1 for cmds in steps for c in cmds if isinstance(c, sched.BackwardPass))
        assert fwd == M and bwd == M
        # optimizer step exactly once, at the last tick
        opt = [i for i, cmds in enumerate(steps) for c in cmds if isinstance(c, sched.OptimizerStep)]
        assert opt == [len(steps) - 1]


def test_train_schedule_fwd_before_bwd():
    M, S = 4, 2
    for stage in range(S):
        s = sched.TrainSchedule(micro_batches=M, stages=S, stage_id=stage)
        seen_fwd = set()
        for cmds in s.steps():
            for c in cmds:
                if isinstance(c, sched.ForwardPass):
                    seen_fwd.add(c.buffer_id)
                if isinstance(c, sched.BackwardPass):
                    assert c.buffer_id in seen_fwd  # 1F1B: bwd after its fwd


def test_train_schedule_buffer_counts():
    s0 = sched.TrainSchedule(micro_batches=8, stages=4, stage_id=0)
    s3 = sched.TrainSchedule(micro_batches=8, stages=4, stage_id=3)
    assert s0.num_pipe_buffers() == 4  # first stage holds most in-flight fwds
    assert s3.num_pipe_buffers() == 2


def test_inference_schedule():
    s = sched.InferenceSchedule(micro_batches=4, stages=2, stage_id=0)
    steps = list(s.steps())
    assert len(steps) == 4 + 2 - 1
    fwd = sum(1 for cmds in steps for c in cmds if isinstance(c, sched.ForwardPass))
    assert fwd == 4


# ---------------------------------------------------------------------------
# PipelineModule partitioning
# ---------------------------------------------------------------------------
def test_pipeline_module_partition():
    cfg = get_gpt2_config("test", n_layer=4)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), num_stages=2)
    assert pipe.n_body == 4 and pipe.layers_per_stage == 2
    assert len(pipe.prologue_specs) == 1 and len(pipe.epilogue_specs) == 2

    with pytest.raises(ValueError, match="divide evenly"):
        PipelineModule(layers=gpt2_pipe_layers(get_gpt2_config("test", n_layer=3)), num_stages=2)


# ---------------------------------------------------------------------------
# numerical parity: pipelined loss == dense-model loss on identical weights
# ---------------------------------------------------------------------------
def _dense_params_from_pipe(pipe_params, n_layer):
    """Remap the pipeline param layout onto GPT2LMHeadModel's layout."""
    dense = {}
    dense["wte"] = pipe_params["tied_embed"]["wte"]
    dense["wpe"] = pipe_params["tied_embed"]["wpe"]
    body = pipe_params["body"]["block"]
    for i in range(n_layer):
        dense[f"h_{i}"] = jax.tree.map(lambda a: a[i], body)
    dense["ln_f"] = pipe_params["epilogue_0"]["ln_f"]
    return dense


def test_pipeline_matches_dense_loss():
    cfg = get_gpt2_config("test", n_layer=4)
    topo = MeshTopology(pipe=2, data=2, fsdp=2)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    ds_config = {
        "train_batch_size": 8,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=pipe, config=ds_config, topology=topo)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    pipe_loss = float(engine.eval_batch(batch))

    set_topology(None)  # dense reference on a plain single-mesh
    dense_params = _dense_params_from_pipe(jax.device_get(engine.state.params), cfg.n_layer)
    model = GPT2LMHeadModel(cfg)
    logits = jax.jit(lambda p, ids: model.apply({"params": p}, ids, deterministic=True))(
        dense_params, jnp.asarray(batch["input_ids"]))
    dense_loss = float(cross_entropy_loss(logits[:, :-1], jnp.asarray(batch["input_ids"])[:, 1:]))

    np.testing.assert_allclose(pipe_loss, dense_loss, rtol=2e-5)


def test_pipeline_trains():
    cfg = get_gpt2_config("test", n_layer=2)
    topo = MeshTopology(pipe=2, data=1, fsdp=4)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    ds_config = {
        "train_batch_size": 16,
        "gradient_accumulation_steps": 4,
        "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
        "zero_optimization": {"stage": 1},
        "gradient_clipping": 1.0,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=pipe, config=ds_config, topology=topo)
    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (16, 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], f"pipeline loss did not fall: {losses}"

    # body params are sharded over the pipe axis
    body_leaf = engine.state.params["body"]["block"]["attn"]["c_attn"]["kernel"]
    assert "pipe" in jax.tree.leaves(tuple(body_leaf.sharding.spec))

    # forward/backward shims are rejected like the reference
    with pytest.raises(RuntimeError):
        engine.forward(batch)


# ---------------------------------------------------------------------------
# tied weights + checkpointing (reference tied-layer grads, pipe ckpt tests)
# ---------------------------------------------------------------------------
def test_tied_embedding_receives_both_gradient_paths():
    """The tied wte is used by the prologue (lookup) AND the epilogue (LM
    head). Its gradient must include both uses — zeroing the head
    contribution would leave only the gather path, so compare against the
    dense model's wte grad, which is the ground truth for the sum."""
    cfg = get_gpt2_config("test", n_layer=2)
    topo = MeshTopology(pipe=2, data=1, fsdp=4)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=pipe, config={"train_batch_size": 8,
                            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        topology=topo)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)

    ids = jnp.asarray(batch["input_ids"])
    pipe_params = jax.device_get(engine.state.params)
    fn = engine._pipeline_loss_fn()
    ids_mb = ids[None]  # [micro=1, batch, seq]

    def pipe_loss(p):
        return fn(p, ids_mb, ids_mb)

    with engine.mesh:
        g_pipe = jax.jit(jax.grad(pipe_loss))(pipe_params)["tied_embed"]["wte"]

    set_topology(None)
    dense_params = _dense_params_from_pipe(pipe_params, cfg.n_layer)
    model = GPT2LMHeadModel(cfg)

    def dense_loss(p):
        logits = model.apply({"params": p}, ids, deterministic=True)
        return cross_entropy_loss(logits[:, :-1], ids[:, 1:])

    g_dense = jax.jit(jax.grad(dense_loss))(dense_params)["wte"]
    np.testing.assert_allclose(np.asarray(g_pipe, np.float32),
                               np.asarray(g_dense, np.float32), atol=2e-5)


def test_pipeline_checkpoint_roundtrip(tmp_path):
    cfg = get_gpt2_config("test", n_layer=2)
    topo = MeshTopology(pipe=2, data=1, fsdp=4)

    def build():
        pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=pipe, config={"train_batch_size": 8,
                                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
            topology=topo)
        return engine

    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    e1 = build()
    for _ in range(2):
        e1.train_batch(batch)
    e1.save_checkpoint(str(tmp_path))

    e2 = build()
    e2.initialize_state(batch)
    e2.load_checkpoint(str(tmp_path))
    assert e2.global_steps == 2
    l1, l2 = float(e1.train_batch(batch)), float(e2.train_batch(batch))
    assert abs(l1 - l2) < 1e-6


# ---------------------------------------------------------------------------
# 4-stage pipeline (nothing validated >2 stages before)
# ---------------------------------------------------------------------------
def test_pipeline_matches_dense_loss_4stage():
    """4 pipeline stages x fsdp, tied embeddings: eval loss must equal the
    dense model's on the same (re-assembled) weights."""
    cfg = get_gpt2_config("test", n_layer=4)
    topo = MeshTopology(pipe=4, data=1, fsdp=2)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=pipe, config={"train_batch_size": 8,
                            "gradient_accumulation_steps": 2,
                            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        topology=topo)
    rng = np.random.default_rng(5)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    pipe_loss = float(engine.eval_batch(batch))

    set_topology(None)
    dense_params = _dense_params_from_pipe(jax.device_get(engine.state.params), cfg.n_layer)
    model = GPT2LMHeadModel(cfg)
    logits = model.apply({"params": dense_params}, jnp.asarray(batch["input_ids"]),
                         deterministic=True)
    dense_loss = float(cross_entropy_loss(logits[:, :-1], jnp.asarray(batch["input_ids"])[:, 1:]))
    np.testing.assert_allclose(pipe_loss, dense_loss, rtol=2e-5)


def test_pipeline_trains_4stage_tied_grads():
    """4-stage training decreases the loss, and the tied wte gradient (used
    by stage 0's lookup and stage 3's head — 3 stages apart) matches the
    dense ground truth."""
    cfg = get_gpt2_config("test", n_layer=4)
    topo = MeshTopology(pipe=4, data=1, fsdp=2)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=pipe, config={"train_batch_size": 8,
                            "gradient_accumulation_steps": 4,
                            "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
                            "zero_optimization": {"stage": 1}},
        topology=topo)
    rng = np.random.default_rng(6)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)

    # tied-grad parity at 4 stages
    ids = jnp.asarray(batch["input_ids"])
    pipe_params = jax.device_get(engine.state.params)
    fn = engine._pipeline_loss_fn()
    ids_mb = ids.reshape(4, 2, 32)  # [micro=4, mb, seq]

    with engine.mesh:
        g_pipe = jax.jit(jax.grad(lambda p: fn(p, ids_mb, ids_mb)))(pipe_params)[
            "tied_embed"]["wte"]
    set_topology(None)
    dense_params = _dense_params_from_pipe(pipe_params, cfg.n_layer)
    model = GPT2LMHeadModel(cfg)

    def dense_loss(p):
        losses = []
        for i in range(4):
            sub = ids[2 * i:2 * i + 2]
            logits = model.apply({"params": p}, sub, deterministic=True)
            losses.append(cross_entropy_loss(logits[:, :-1], sub[:, 1:]))
        return jnp.mean(jnp.stack(losses))

    g_dense = jax.jit(jax.grad(dense_loss))(dense_params)["wte"]
    np.testing.assert_allclose(np.asarray(g_pipe, np.float32),
                               np.asarray(g_dense, np.float32), atol=2e-5)

    set_topology(engine.topology)
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], f"4-stage pipeline loss did not fall: {losses}"


def test_scan_matches_train_schedule_parity_4stage():
    """The scan engine's tick structure is the TrainSchedule's: per stage M
    forwards + M backwards in 2(M+S-1) ticks, and the scan's forward span
    (micro + stages - 1) equals the schedule's last ForwardPass tick + 1 —
    at 4 stages."""
    cfg = get_gpt2_config("test", n_layer=4)
    topo = MeshTopology(pipe=4, data=1, fsdp=2)
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=pipe, config={"train_batch_size": 8,
                            "gradient_accumulation_steps": 4,
                            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}},
        topology=topo)
    M, S = engine.micro_batches, engine.pipeline.num_stages
    assert S == 4 and M == 4
    scan_fwd_ticks = M + S - 1  # the engine's n_ticks (pipe/engine.py tick loop)
    last_fwd_tick = -1
    for stage in range(S):
        steps = list(engine._reference_schedule(stage).steps())
        assert len(steps) == 2 * (M + S - 1)
        fwd_ticks = [i for i, cmds in enumerate(steps)
                     for c in cmds if isinstance(c, sched.ForwardPass)]
        assert len(fwd_ticks) == M
        last_fwd_tick = max(last_fwd_tick, *fwd_ticks)
        bwd = sum(1 for cmds in steps for c in cmds if isinstance(c, sched.BackwardPass))
        assert bwd == M
    # interleaving differs BY DESIGN: TrainSchedule is 1F1B (stage s runs
    # fwd of micro m at tick s + 2m — each later micro waits out one bwd
    # slot), while the scan engine is GPipe-ordered (fwd at tick s + m; the
    # backward is the scan's transpose) with remat playing 1F1B's
    # memory-bounding role. The schedules agree on the instruction
    # multiset (asserted above) and the tick algebra maps one onto the
    # other: reference_last_fwd = scan_last_fwd + (M - 1).
    assert last_fwd_tick == (scan_fwd_ticks - 1) + (M - 1), (last_fwd_tick, scan_fwd_ticks)
