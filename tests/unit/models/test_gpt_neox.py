"""GPT-NeoX family: HF parity (parallel and serial residual), decode-cache
equivalence, training. Reference: module_inject/containers/gptneox.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPTNeoXForCausalLM, get_gpt_neox_config


def test_neox_decode_matches_full_forward():
    cfg = get_gpt_neox_config("test")
    model = GPTNeoXForCausalLM(cfg)
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


def test_neox_trains_under_engine():
    cfg = get_gpt_neox_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPTNeoXForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    })
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("parallel", [True, False])
def test_hf_neox_checkpoint_parity(parallel):
    """HF torch GPT-NeoX logits == converted deepspeed_tpu logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_gpt_neox

    hf_cfg = transformers.GPTNeoXConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                                        num_hidden_layers=2, num_attention_heads=4,
                                        max_position_embeddings=64, rotary_pct=0.25,
                                        use_parallel_residual=parallel,
                                        hidden_dropout=0.0, attention_dropout=0.0)
    hf_model = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    cfg = get_gpt_neox_config("test", vocab_size=128, hidden_size=32, intermediate_size=64,
                              num_hidden_layers=2, num_attention_heads=4,
                              max_position_embeddings=64, rotary_pct=0.25,
                              use_parallel_residual=parallel)
    params = load_hf_gpt_neox(hf_model, cfg)
    ids_np = np.random.default_rng(2).integers(0, 128, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids_np)).logits.numpy()
    ours = GPTNeoXForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids_np, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=3e-4, rtol=3e-3)
