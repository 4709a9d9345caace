"""BLOOM family: alibi attention, HF parity, decode-cache equivalence.
Reference: module_inject/containers/bloom.py + alibi softmax kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import BloomForCausalLM, get_bloom_config
from deepspeed_tpu.models.bloom import alibi_slopes


@pytest.mark.parametrize("n", [4, 8, 16])
def test_alibi_slopes_power_of_two(n):
    s = np.asarray(alibi_slopes(n))
    assert s.shape == (n,) and (s > 0).all() and (np.diff(s) < 0).all()


def test_alibi_slopes_non_power_of_two():
    s = np.asarray(alibi_slopes(6))
    assert s.shape == (6,) and (s > 0).all()


def test_bloom_decode_matches_full_forward():
    cfg = get_bloom_config("test")
    model = BloomForCausalLM(cfg)
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


def test_bloom_trains_under_engine():
    cfg = get_bloom_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=BloomForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
    })
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


def test_hf_bloom_checkpoint_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_bloom

    hf_cfg = transformers.BloomConfig(vocab_size=128, hidden_size=32, n_head=4, n_layer=2,
                                      hidden_dropout=0.0, attention_dropout=0.0)
    hf_model = transformers.BloomForCausalLM(hf_cfg).eval()
    cfg = get_bloom_config("test", vocab_size=128, hidden_size=32, n_head=4, n_layer=2)
    params = load_hf_bloom(hf_model, cfg)
    ids_np = np.random.default_rng(2).integers(0, 128, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids_np)).logits.numpy()
    ours = BloomForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids_np, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=3e-4, rtol=3e-3)
