"""GPT-J family: HF parity (interleaved rotary, shared-LN parallel residual,
biased lm_head), decode-cache equivalence, training.
Reference: module_inject/containers/gptj.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPTJForCausalLM, get_gptj_config


def test_interleaved_rotary_differs_from_half_split():
    """Guard the convention: GPT-J's rotate-every-two must NOT match the
    NeoX/LLaMA half-split on the same inputs (they agree only at D=2)."""
    from deepspeed_tpu.models.gptj import rotary_embedding_interleaved
    from deepspeed_tpu.models.llama import rotary_embedding

    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 4, 2, 8)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(4)[None, :], (1, 4))
    a = rotary_embedding_interleaved(x, pos)
    b = rotary_embedding(x, pos)
    assert not np.allclose(np.asarray(a), np.asarray(b), atol=1e-3)
    # both are rotations: norms preserved per head vector
    np.testing.assert_allclose(np.linalg.norm(np.asarray(a), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def test_gptj_decode_matches_full_forward():
    cfg = get_gptj_config("test")
    model = GPTJForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 10)), jnp.int32)
    # init, the full pass and the step jitted: eagerly each is dispatched an operation at a time
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    full = jax.jit(model.apply)({"params": params}, ids)
    decode = jax.jit(lambda cache, token: model.apply({"params": params, "cache": cache}, token,
                                                      decode=True, mutable=["cache"]))
    from deepspeed_tpu.models.common import init_cache
    cache = init_cache(model, batch_size=2)
    outs = []
    for t in range(ids.shape[1]):
        step, mut = decode(cache, ids[:, t:t + 1])
        cache = mut["cache"]
        outs.append(step)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(full),
                               atol=2e-4, rtol=2e-4)


def test_gptj_trains_under_engine():
    cfg = get_gptj_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPTJForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    })
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


def test_hf_gptj_checkpoint_parity():
    """HF torch GPT-J logits == converted deepspeed_tpu logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_gptj

    hf_cfg = transformers.GPTJConfig(vocab_size=128, n_embd=32, n_layer=2, n_head=4,
                                     n_inner=64, n_positions=64, rotary_dim=4,
                                     resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    hf_model = transformers.GPTJForCausalLM(hf_cfg).eval()
    cfg = get_gptj_config("test", vocab_size=128, hidden_size=32, intermediate_size=64,
                          num_hidden_layers=2, num_attention_heads=4,
                          max_position_embeddings=64, rotary_dim=4)
    params = load_hf_gptj(hf_model, cfg)
    ids_np = np.random.default_rng(2).integers(0, 128, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids_np)).logits.numpy()
    ours = GPTJForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids_np, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=3e-4, rtol=3e-3)
