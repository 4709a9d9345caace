"""Config-ladder build checks (BASELINE.md rungs): the judged large-model
configurations must TRACE AND LOWER on a multi-device mesh — abstract
shapes only, no parameter materialization — so scale-relevant breakage
(sharding mismatches, planner errors, qcomm composition) surfaces in CI
rather than on hardware. Compilation/runtime cost is the bench's job."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import (GPT2LMHeadModel, LlamaForCausalLM, get_gpt2_config,
                                  get_llama_config)
from deepspeed_tpu.parallel.topology import MeshTopology


def _lower(model, ds_config, topology, seq=128, batch=8):
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, topology=topology,
                                               config=ds_config)
    batch_np = {"input_ids": np.zeros((batch, seq), np.int32)}
    lowered = engine.lower_train_step(batch_np)
    text = lowered.as_text()
    assert text and "func" in text
    return engine, text


def _param_count(engine):
    import jax
    return sum(int(np.prod(sh)) for sh in jax.tree.leaves(
        engine.plan.param_shapes, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.mark.parametrize("stage", [2, 3])
def test_gpt2_xl_lowers_under_zero(stage):
    """GPT-2-XL (1.5B) bf16 ZeRO-2/3 over fsdp=8 — the ladder's second rung."""
    import jax.numpy as jnp
    cfg = get_gpt2_config("xl", n_positions=128, dtype=jnp.bfloat16, remat=True)
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
          "bf16": {"enabled": True},
          "zero_optimization": {"stage": stage}}
    engine, text = _lower(GPT2LMHeadModel(cfg), ds, MeshTopology(fsdp=8))
    assert _param_count(engine) > 1.5e9


def test_llama_1b_lowers_with_zeropp_and_tp():
    """LLaMA-family rung with ZeRO++ quantized collectives composing with
    tensor parallelism (fsdp=4 x tensor=2)."""
    import jax.numpy as jnp

    cfg = get_llama_config("1b", max_position_embeddings=128, dtype=jnp.bfloat16, remat=True)
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
          "bf16": {"enabled": True},
          "zero_optimization": {"stage": 3,
                                "zero_quantized_weights": True,
                                "zero_quantized_gradients": True}}
    engine, text = _lower(LlamaForCausalLM(cfg), ds, MeshTopology(fsdp=4, tensor=2))
    assert engine._use_qcomm, "qcomm must engage on a DP(+TP) mesh"


def test_llama_7b_lowers_full_stack():
    """The ladder's top rung at full scale: LLaMA-7B bf16, ZeRO-3 +
    ZeRO++ quantized collectives, tensor=2 x sequence=2 x fsdp=2, remat,
    fused LM-head loss — the training graph must build abstractly (no 7B
    of host RAM touched; lower() only)."""
    import jax.numpy as jnp
    cfg = get_llama_config("7b", max_position_embeddings=128, dtype=jnp.bfloat16,
                           remat=True, fused_head_loss_chunk=128)
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
          "bf16": {"enabled": True},
          "zero_optimization": {"stage": 3,
                                "zero_quantized_weights": True,
                                "zero_quantized_gradients": True}}
    engine, text = _lower(LlamaForCausalLM(cfg), ds,
                          MeshTopology(fsdp=2, tensor=2, sequence=2))
    assert _param_count(engine) > 6e9  # the real 7B count, planned and sharded


def test_gpt_moe_350m_64e_lowers_under_ep():
    """The ladder's MoE rung: GPT-MoE 350M-base x 64 experts, expert
    parallel over expert=8 (8 local experts per device), ZeRO-1 for the
    dense grads — the training graph must plan and lower."""
    import jax.numpy as jnp
    cfg = get_gpt2_config("350m", n_positions=128, dtype=jnp.bfloat16, remat=True,
                          moe_num_experts=64, moe_layer_freq=2, moe_k=1)
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
          "bf16": {"enabled": True},
          "zero_optimization": {"stage": 1}}
    engine, text = _lower(GPT2LMHeadModel(cfg), ds, MeshTopology(expert=8))
    # 64 experts' FFNs dominate: far above the 355M dense base
    assert _param_count(engine) > 1e9
    # the dispatch collective only appears post-SPMD: compile the same
    # topology at unit scale and assert the a2a is on the wire
    import numpy as np
    small = get_gpt2_config("test", moe_num_experts=8, moe_layer_freq=2, moe_k=1)
    eng2, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(small), topology=MeshTopology(expert=8),
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, small.vocab_size, (8, 32)).astype(np.int32)}
    eng2.initialize_state(batch)
    assert "all-to-all" in eng2.lower_train_step(batch).compile().as_text()


def test_moe_serving_tp8_generates():
    """The ladder's serving rung: expert-parallel GPT-MoE served through
    init_inference at TP=8 on the virtual mesh — runs, not just lowers."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    cfg = get_gpt2_config("test", n_embd=128, n_head=8, moe_num_experts=8,
                          moe_layer_freq=2, moe_k=1)
    model = GPT2LMHeadModel(cfg)
    ids = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    variables = jax.jit(lambda key: model.init(key, jnp.asarray(ids), deterministic=True))(
        jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(model, config={"dtype": "fp32"}, mp_size=8,
                                          params=variables["params"])
    out = engine.generate(ids, max_new_tokens=4)
    assert out.shape == (2, 12)
    assert np.isfinite(np.asarray(out)).all()
    # TP actually engaged: at least one served weight is sharded on tensor
    from jax.sharding import PartitionSpec as P
    flat = jax.tree.leaves(engine.param_specs, is_leaf=lambda x: isinstance(x, P))
    assert any("tensor" in str(s) for s in flat)
