"""OPT family: HF checkpoint parity, decode-cache equivalence, training.
Reference coverage model: module_inject/containers/opt.py + HF OPT tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import OPTForCausalLM, get_opt_config


def test_opt_forward_shapes():
    cfg = get_opt_config("test")
    model = OPTForCausalLM(cfg)
    ids = jnp.asarray(np.arange(2 * 16).reshape(2, 16) % cfg.vocab_size, jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_opt_decode_matches_full_forward():
    cfg = get_opt_config("test")
    model = OPTForCausalLM(cfg)
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
    decoded = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(full), atol=2e-4, rtol=2e-4)


def test_opt_trains_under_engine():
    cfg = get_opt_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=OPTForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
    })
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


def test_hf_opt_checkpoint_parity():
    """HF torch OPT logits == converted deepspeed_tpu logits (125m-style and
    350m-style with project_in/out + post-LN)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_opt

    for style in ("pre_ln", "post_ln_proj"):
        if style == "pre_ln":
            hf_cfg = transformers.OPTConfig(vocab_size=128, hidden_size=32, ffn_dim=64,
                                            num_hidden_layers=2, num_attention_heads=4,
                                            max_position_embeddings=64, do_layer_norm_before=True,
                                            word_embed_proj_dim=32, dropout=0.0)
            cfg = get_opt_config("test", vocab_size=128, hidden_size=32, ffn_dim=64,
                                 num_hidden_layers=2, num_attention_heads=4,
                                 max_position_embeddings=64, do_layer_norm_before=True)
        else:
            hf_cfg = transformers.OPTConfig(vocab_size=128, hidden_size=32, ffn_dim=64,
                                            num_hidden_layers=2, num_attention_heads=4,
                                            max_position_embeddings=64, do_layer_norm_before=False,
                                            word_embed_proj_dim=16, dropout=0.0)
            cfg = get_opt_config("test", vocab_size=128, hidden_size=32, ffn_dim=64,
                                 num_hidden_layers=2, num_attention_heads=4,
                                 max_position_embeddings=64, do_layer_norm_before=False,
                                 word_embed_proj_dim=16)
        hf_model = transformers.OPTForCausalLM(hf_cfg).eval()
        params = load_hf_opt(hf_model, cfg)
        ids_np = np.random.default_rng(2).integers(0, 128, (2, 12))
        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(ids_np)).logits.numpy()
        ours = OPTForCausalLM(cfg).apply({"params": params},
                                         jnp.asarray(ids_np, jnp.int32))
        np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=2e-4, rtol=2e-3), style


def test_has_embed_proj_hf_equal_dims():
    """HF sets word_embed_proj_dim == hidden_size for non-350m models; that
    must mean NO projection layers (mirroring an HF config must not create
    phantom project_in/out params)."""
    cfg = get_opt_config("test", word_embed_proj_dim=64)  # == hidden_size
    assert not cfg.has_embed_proj
    model = OPTForCausalLM(cfg)
    ids = jnp.zeros((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert "project_in" not in params and "project_out" not in params
    cfg2 = get_opt_config("test", word_embed_proj_dim=32)
    assert cfg2.has_embed_proj
