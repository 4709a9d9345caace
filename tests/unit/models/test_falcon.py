"""Falcon family: MQA (7B-style) and GQA/new-arch (40B-style) HF parity,
decode-cache equivalence, engine training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import FalconForCausalLM, get_falcon_config


@pytest.mark.parametrize("preset", ["test", "test-gqa"])
def test_falcon_decode_matches_full_forward(preset):
    cfg = get_falcon_config(preset)
    model = FalconForCausalLM(cfg)
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


def test_falcon_trains_under_engine():
    cfg = get_falcon_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=FalconForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
    })
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("new_arch", [False, True])
def test_hf_falcon_checkpoint_parity(new_arch):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "FalconForCausalLM"):
        pytest.skip("transformers too old for Falcon")
    from deepspeed_tpu.module_inject import load_hf_falcon

    kv = 2 if new_arch else 1
    hf_cfg = transformers.FalconConfig(vocab_size=128, hidden_size=32,
                                       num_attention_heads=4, num_kv_heads=kv,
                                       num_hidden_layers=2, parallel_attn=True,
                                       bias=False, alibi=False,
                                       new_decoder_architecture=new_arch,
                                       multi_query=not new_arch,
                                       attention_dropout=0.0, hidden_dropout=0.0)
    torch.manual_seed(0)
    hf = transformers.FalconForCausalLM(hf_cfg).eval()
    cfg = get_falcon_config("test", vocab_size=128, hidden_size=32,
                            num_attention_heads=4, num_kv_heads=kv,
                            num_hidden_layers=2, new_decoder_architecture=new_arch)
    params = load_hf_falcon(hf, cfg)
    ids = np.random.default_rng(2).integers(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = FalconForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, atol=3e-4, rtol=3e-3)


def test_unsupported_falcon_variants_rejected():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "FalconForCausalLM"):
        pytest.skip("transformers too old for Falcon")
    from deepspeed_tpu.module_inject import load_hf_falcon
    cfg = get_falcon_config("test")
    rw = transformers.FalconConfig(vocab_size=64, hidden_size=32, num_attention_heads=4,
                                   num_hidden_layers=1, alibi=True, parallel_attn=False,
                                   multi_query=False, new_decoder_architecture=False)
    hf = transformers.FalconForCausalLM(rw).eval()
    with pytest.raises(ValueError):
        load_hf_falcon(hf, cfg)
