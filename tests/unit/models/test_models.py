"""Model-zoo tests: LLaMA (RoPE/GQA/SwiGLU, decode cache) and BERT (MLM),
shape/numerics smoke + engine training on the 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import (BertForMaskedLM, LlamaForCausalLM, bert_mlm_loss, get_bert_config,
                                  get_llama_config)
from deepspeed_tpu.models.llama import rotary_embedding
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


def test_rotary_embedding_properties():
    # norm preservation and relative-position property
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8)[None], (1, 8))
    r = rotary_embedding(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # dot(q_i, k_j) depends only on i-j: shift both by +3 and compare
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    def dot_at(pi, pj):
        qi = rotary_embedding(q, jnp.full((1, 1), pi))
        kj = rotary_embedding(k, jnp.full((1, 1), pj))
        return float(jnp.sum(qi * kj))
    np.testing.assert_allclose(dot_at(5, 2), dot_at(8, 5), rtol=1e-5)


def test_llama_forward_and_shapes():
    cfg = get_llama_config("test")
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    import flax.linen as nn
    variables = model.init(jax.random.PRNGKey(0), ids)
    logits = model.apply(variables, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # GQA: kv projections have fewer heads
    k_kernel = nn.meta.unbox(variables["params"])["layers_0"]["self_attn"]["k_proj"]["kernel"]
    q_kernel = nn.meta.unbox(variables["params"])["layers_0"]["self_attn"]["q_proj"]["kernel"]
    assert k_kernel.shape[1] == cfg.num_key_value_heads
    assert q_kernel.shape[1] == cfg.num_attention_heads


def test_llama_decode_cache_matches_full_forward():
    """Prefill+incremental decode logits == full forward logits."""
    cfg = get_llama_config("test")
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    # init, the full pass and the step jitted: eagerly each is dispatched an operation at a time
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    full = jax.jit(model.apply)(variables, ids)
    decode = jax.jit(lambda cache, fed: model.apply({**variables, **cache}, fed, decode=True,
                                                    mutable=["cache"]))

    # prefill on the first 8 tokens, then decode 4 more one at a time
    from deepspeed_tpu.models.llama import init_cache
    cache = {"cache": init_cache(model, batch_size=2)}
    out, cache = decode(cache, ids[:, :8])
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, :8]), rtol=2e-4, atol=2e-4)
    for t in range(8, 12):
        out, cache = decode(cache, ids[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_llama_trains_zero3_tp():
    cfg = get_llama_config("test")
    topo = MeshTopology(tensor=2, data=1, fsdp=4)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        config={"train_batch_size": 8, "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3, "stage3_param_persistence_threshold": 0}},
        topology=topo)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    # TP: gate_proj sharded over tensor axis, fsdp pass applied too (zero3)
    kern = engine.state.params["layers_0"]["mlp"]["gate_proj"]["kernel"]
    flat = jax.tree.leaves(tuple(kern.sharding.spec))
    assert "tensor" in flat and "fsdp" in flat, kern.sharding.spec


def test_bert_mlm_trains():
    cfg = get_bert_config("test")
    topo = MeshTopology(fsdp=8, data=1)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=BertForMaskedLM(cfg),
        config={"train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 2e-3}},
                "zero_optimization": {"stage": 1}},
        topology=topo, loss_fn=bert_mlm_loss)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    labels = np.where(rng.random((8, 32)) < 0.15, ids, -100).astype(np.int32)
    batch = {"input_ids": ids, "labels": labels}
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


def test_embed_onehot_grad_matches_scatter():
    """The one-hot-matmul backward must produce the same embedding gradient
    as the scatter-add backward (models/common.embed_lookup perf knob)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.common import embed_lookup
    rng = np.random.default_rng(0)
    wte = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 32, (2, 16)), jnp.int32)

    def loss(w, onehot):
        x = embed_lookup(w, ids, onehot)
        return (x * jnp.arange(1, 9)).sum()

    g_scatter = jax.grad(lambda w: loss(w, False))(wte)
    g_onehot = jax.grad(lambda w: loss(w, True))(wte)
    np.testing.assert_allclose(np.asarray(g_onehot), np.asarray(g_scatter),
                               atol=1e-5, rtol=1e-5)


def test_gpt2_embed_onehot_grad_trains_identically():
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    import deepspeed_tpu
    ids = np.random.default_rng(1).integers(0, 256, (8, 32)).astype(np.int32)

    def train(onehot):
        cfg = get_gpt2_config("test", embed_onehot_grad=onehot)
        e, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
        e.initialize_state({"input_ids": ids})
        losses = [float(e.train_batch({"input_ids": ids})) for _ in range(3)]
        return losses

    np.testing.assert_allclose(train(True), train(False), atol=1e-4)


def test_mixtral_style_llama_moe_trains_and_serves():
    """Mixtral shape: llama blocks with top-2-of-N expert FFNs. Trains under
    the engine (aux loss plumbed), serves through init_inference."""
    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaForCausalLM, get_llama_config
    cfg = get_llama_config("mixtral-test")
    assert cfg.moe_num_experts == 4 and cfg.moe_k == 2
    engine, _, _, _ = deepspeed_tpu.initialize(model=LlamaForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses

    params = jax.device_get(engine.state.params)
    ie = deepspeed_tpu.init_inference(LlamaForCausalLM(cfg), config={"dtype": "fp32"},
                                     params=params)
    out = ie.generate(batch["input_ids"][:2, :8], max_new_tokens=3)
    assert out.shape == (2, 11) and np.isfinite(np.asarray(out)).all()


def test_mixtral_hf_checkpoint_converts():
    """HF Mixtral checkpoints (block_sparse_moe.{gate,experts.N.w1/w2/w3})
    map onto the llama-MoE param tree: structure matches init exactly and
    the converted model runs finite logits. (Exact logits parity is not
    asserted: HF routes dense top-2 while ours uses capacity-based GShard
    dispatch — same experts, different overflow handling.)"""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "MixtralForCausalLM"):
        pytest.skip("transformers too old for Mixtral")
    from deepspeed_tpu.models import LlamaForCausalLM, get_llama_config
    from deepspeed_tpu.module_inject import load_hf_llama

    hf_cfg = transformers.MixtralConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                                        num_hidden_layers=2, num_attention_heads=4,
                                        num_key_value_heads=2, max_position_embeddings=64,
                                        num_local_experts=4, num_experts_per_tok=2,
                                        attention_dropout=0.0)
    hf = transformers.MixtralForCausalLM(hf_cfg).eval()
    cfg = get_llama_config("mixtral-test", vocab_size=128, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=64, moe_num_experts=4, moe_k=2)
    params = load_hf_llama(hf, cfg)

    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 8), jnp.int32)
    from flax.core import meta
    ref_tree = jax.tree_util.tree_structure(
        meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"]))
    got_tree = jax.tree_util.tree_structure(params)
    assert ref_tree == got_tree, f"param tree mismatch:\n{ref_tree}\nvs\n{got_tree}"
    logits, aux = model.apply({"params": params}, ids)
    assert logits.shape == (2, 8, 128) and np.isfinite(np.asarray(logits)).all()


def test_qwen2_hf_checkpoint_parity():
    """Qwen2 = llama + biased q/k/v: converted logits match HF torch."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Qwen2ForCausalLM"):
        pytest.skip("transformers too old for Qwen2")
    from deepspeed_tpu.models import LlamaForCausalLM, get_llama_config
    from deepspeed_tpu.module_inject import load_hf_llama

    hf_cfg = transformers.Qwen2Config(vocab_size=128, hidden_size=32, intermediate_size=64,
                                      num_hidden_layers=2, num_attention_heads=4,
                                      num_key_value_heads=2, max_position_embeddings=64,
                                      attention_dropout=0.0, tie_word_embeddings=False)
    hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    cfg = get_llama_config("test", vocab_size=128, hidden_size=32, intermediate_size=64,
                           num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=64, attention_bias=True)
    params = load_hf_llama(hf, cfg)
    ids = np.random.default_rng(3).integers(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = LlamaForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, atol=3e-4, rtol=3e-3)


def test_llama_remat_policy_same_numerics():
    """remat_policy/remat_every on llama (GPT-2 parity): identical outputs
    with and without checkpointing, any policy."""
    from deepspeed_tpu.models import LlamaForCausalLM, get_llama_config
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)), jnp.int32)
    base = get_llama_config("test")
    params = LlamaForCausalLM(base).init(jax.random.PRNGKey(0), ids)["params"]
    ref = LlamaForCausalLM(base).apply({"params": params}, ids)
    for kw in ({"remat": True}, {"remat": True, "remat_policy": "dots_saveable"},
               {"remat": True, "remat_every": 2}):
        cfg = get_llama_config("test", **kw)
        out = LlamaForCausalLM(cfg).apply({"params": params}, ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
        # remat contract: gradients equal the non-remat reference, not just finite
        g = jax.grad(lambda p: LlamaForCausalLM(cfg).apply({"params": p}, ids).sum())(params)
        g_ref = jax.grad(lambda p: LlamaForCausalLM(base).apply({"params": p}, ids).sum())(params)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
