"""Staged knowledge distillation + layer reduction
(reference ``compression/scheduler.py`` + ``compress.py:119``
``teacher_model`` path / ``student_initialization`` ``compress.py:192``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.compression.compress import init_compression, student_initialization
from deepspeed_tpu.compression.scheduler import compression_scheduler
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.parallel.topology import MeshTopology


def _teacher():
    cfg = get_gpt2_config("test", n_layer=4)
    module = GPT2LMHeadModel(cfg)
    import flax.linen as fnn
    params = fnn.meta.unbox(module.init(jax.random.PRNGKey(7),
                                        jnp.zeros((1, 8), jnp.int32),
                                        deterministic=True))["params"]
    return module, jax.device_get(params), cfg


def _student_engine(ds_extra, n_layer=2):
    cfg = get_gpt2_config("test", n_layer=n_layer)
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          **ds_extra}
    eng, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg),
                                            topology=MeshTopology(data=8), config=ds)
    return eng, cfg


LR_BLOCK = {"layer_reduction": {"enabled": True, "keep_number_layer": 2,
                                "module_name_prefix": "transformer.h",
                                "teacher_layer": [1, 3],
                                "other_module_name": ["transformer.wte", "transformer.ln_f"]}}


def test_student_initialization_maps_layers():
    _, t_params, _ = _teacher()
    cfg = get_gpt2_config("test", n_layer=2)
    import flax.linen as fnn
    s_params = jax.device_get(fnn.meta.unbox(GPT2LMHeadModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), deterministic=True))["params"])
    new = student_initialization(s_params, t_params, {"compression_training": LR_BLOCK})
    # student layer 0 <- teacher layer 1, student layer 1 <- teacher layer 3
    for s_key, t_key in (("h_0", "h_1"), ("h_1", "h_3")):
        a = jax.tree.leaves(new[s_key])
        b = jax.tree.leaves(t_params[t_key])
        assert all(np.array_equal(x, y) for x, y in zip(a, b)), (s_key, t_key)
    assert np.array_equal(new["wte"], t_params["wte"])
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(new["ln_f"]), jax.tree.leaves(t_params["ln_f"])))
    # untouched student layers... there are none (both re-seeded); wpe stays
    assert np.array_equal(new["wpe"], s_params["wpe"])


def test_teacher_required_when_layer_reduction_enabled():
    eng, _ = _student_engine({"compression_training": LR_BLOCK})
    with pytest.raises(ValueError, match="Teacher model is required"):
        init_compression(eng, {"compression_training": LR_BLOCK})


def test_distillation_end_to_end_loss_decreases_and_gates_observed():
    """Distill the 4-layer teacher onto a 2-layer student: layer_reduction
    seeds the student, the KD terms activate at schedule_offset (observed:
    pre-offset steps match a no-teacher run bitwise; post-offset steps
    diverge), and the distillation loss decreases."""
    t_module, t_params, _ = _teacher()
    kd_block = {"compression_training": {
        **LR_BLOCK,
        "knowledge_distillation": {"enabled": True, "kd_coef": 0.5,
                                   "temperature": 2.0, "layerwise_coef": 0.1,
                                   "schedule_offset": 2}}}

    # ONE fixed batch: memorizable, so "the objective decreases" is a real
    # training signal rather than noise-fitting luck
    rng = np.random.RandomState(3)
    fixed = {"input_ids": rng.randint(0, 256, (8, 16)).astype(np.int32)}

    eng_kd, cfg = _student_engine(kd_block)
    eng_kd.initialize_state({"input_ids": np.zeros((8, 16), np.int32)})
    init_compression(eng_kd, kd_block, teacher_model=(t_module, t_params))
    l_kd = [float(jnp.asarray(eng_kd.train_batch(fixed))) for _ in range(6)]

    # comparison run: same student init INCLUDING the layer_reduction seed
    # but no KD terms — so any post-offset divergence is the KD gate
    eng_ref, cfg = _student_engine({})
    eng_ref.initialize_state({"input_ids": np.zeros((8, 16), np.int32)})
    # owned copy: the seeded tree aliases teacher host buffers and this
    # state gets DONATED every step (utils/device.py)
    from deepspeed_tpu.utils.device import owned_device_put
    eng_ref.state = eng_ref.state._replace(params=owned_device_put(
        student_initialization(jax.device_get(eng_ref.state.params), t_params,
                               {"compression_training": LR_BLOCK}),
        eng_ref.state_shardings.params))
    l_ref = [float(jnp.asarray(eng_ref.train_batch(fixed))) for _ in range(6)]

    # schedule gate: steps 0,1 are pure CE — bitwise equal to the reference
    # run; the mixed loss kicks in at step 2 and changes the values
    assert l_kd[0] == l_ref[0] and l_kd[1] == l_ref[1], (l_kd[:2], l_ref[:2])
    assert any(a != b for a, b in zip(l_kd[2:], l_ref[2:])), (l_kd, l_ref)
    # the distillation objective trains: mixed loss decreases over the window
    assert l_kd[-1] < l_kd[2], l_kd


def test_scheduler_flags_flip_at_offsets():
    cfg = {"compression_training": {
        "sparse_pruning": {"shared_parameters": {"enabled": True, "schedule_offset": 3},
                           "different_groups": {"g": {"params": {"dense_ratio": 0.5},
                                                      "modules": ["*"]}}}}}
    sched = compression_scheduler(model=None, compression_config=cfg)
    assert not sched.is_active("sparse_pruning")
    for _ in range(2):
        sched.step()
    assert not sched.verbose["sparse_pruning"]
    sched.step()  # training_steps == 3 -> at offset
    assert sched.is_active("sparse_pruning") and sched.verbose["sparse_pruning"]


def test_kd_rejects_bare_flax_module_teacher():
    """A bare flax Module has no weights — distilling against a fresh init
    must be rejected, not silently accepted."""
    eng, _ = _student_engine({})
    t_module, _, _ = _teacher()
    with pytest.raises(TypeError, match="bare flax Module"):
        init_compression(eng, {"compression_training": {
            "knowledge_distillation": {"enabled": True}}}, teacher_model=t_module)


def test_kd_rejects_host_optimizer_paths():
    """offload/1-bit schedules never reach the in-graph KD gate: loud error
    instead of silent pure-CE training with a dead teacher forward."""
    t_module, t_params, _ = _teacher()
    eng, _ = _student_engine({"bf16": {"enabled": True},
                              "zero_optimization": {"stage": 1,
                                                    "offload_optimizer": {"device": "cpu"}}})
    with pytest.raises(ValueError, match="fused train_batch path"):
        init_compression(eng, {"compression_training": {
            "knowledge_distillation": {"enabled": True}}},
            teacher_model=(t_module, t_params))


def test_kd_rejects_fused_head():
    cfg = get_gpt2_config("test", n_layer=2, fused_head_loss_chunk=64)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), topology=MeshTopology(data=8),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    t_module, t_params, _ = _teacher()
    init_compression(eng, {"compression_training": {
        "knowledge_distillation": {"enabled": True}}},
        teacher_model=(t_module, t_params))
    with pytest.raises(ValueError, match="fused_head"):
        eng.train_batch({"input_ids": np.zeros((8, 16), np.int32)})
