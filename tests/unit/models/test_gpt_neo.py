"""GPT-Neo family: HF parity (unscaled attention, alternating global/local
layers), local-window masking, decode-cache equivalence, training.
Reference: module_inject/containers/gptneo.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPTNeoForCausalLM, get_gpt_neo_config


def test_local_layer_masks_beyond_window():
    """The odd (local) layer must ignore keys further than window_size
    back. Layer 0 is global, layer 1 local (index-based), so: zero the
    global layer's value path and a distant-past perturbation must be
    invisible at the last position; restore it and the perturbation must
    show (global attention sees the whole prefix)."""
    cfg2 = get_gpt_neo_config("test", num_hidden_layers=2, window_size=4)
    model = GPTNeoForCausalLM(cfg2)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg2.vocab_size, (1, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    base = model.apply({"params": params}, ids)
    far = ids.at[0, 2].set((int(ids[0, 2]) + 1) % cfg2.vocab_size)
    out = model.apply({"params": params}, far)
    # token 2 is outside the last position's local window (16-4=12 > 2) but
    # inside its global attention — logits at the last position must differ
    # (global layer sees it), and the LOCAL layer's own contribution at
    # position 15 must not depend on it. Verify the window actually bites:
    # zero the global layer's value path so only the local layer carries
    # attention information; then the last position must be unchanged.
    import flax.linen as nn

    def zeroed(p):
        return (p.replace_boxed(jnp.zeros_like(p.unbox()))
                if isinstance(p, nn.meta.AxisMetadata) else jnp.zeros_like(p))

    p2 = jax.tree.map(lambda x: x, params)
    p2["h_0"]["attn"]["v_proj"]["kernel"] = zeroed(p2["h_0"]["attn"]["v_proj"]["kernel"])
    p2["h_0"]["attn"]["out_proj"]["kernel"] = zeroed(p2["h_0"]["attn"]["out_proj"]["kernel"])
    a = model.apply({"params": p2}, ids)[0, -1]
    b = model.apply({"params": p2}, far)[0, -1]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert not np.allclose(np.asarray(base[0, -1]), np.asarray(out[0, -1]), atol=1e-6)


def test_gpt_neo_decode_matches_full_forward():
    cfg = get_gpt_neo_config("test", window_size=32)  # window >= seq: decode parity
    model = GPTNeoForCausalLM(cfg)
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


def test_gpt_neo_trains_under_engine():
    cfg = get_gpt_neo_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPTNeoForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    })
    batch = {"input_ids": np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


def test_hf_gpt_neo_checkpoint_parity():
    """HF torch GPT-Neo logits == converted deepspeed_tpu logits, with one
    global and one local layer in play."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_gpt_neo

    hf_cfg = transformers.GPTNeoConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position_embeddings=64, window_size=4,
        attention_types=[[["global", "local"], 1]],
        resid_dropout=0.0, embed_dropout=0.0, attention_dropout=0.0)
    hf_model = transformers.GPTNeoForCausalLM(hf_cfg).eval()
    cfg = get_gpt_neo_config("test", vocab_size=128, hidden_size=32, num_hidden_layers=2,
                             num_attention_heads=4, intermediate_size=64,
                             max_position_embeddings=64, window_size=4)
    params = load_hf_gpt_neo(hf_model, cfg)
    ids_np = np.random.default_rng(2).integers(0, 128, (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids_np)).logits.numpy()
    ours = GPTNeoForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids_np, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=3e-4, rtol=3e-3)


def test_converter_rejects_mismatched_schedule():
    """All-global or different-window HF checkpoints must be rejected, not
    silently mis-masked."""
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_gpt_neo

    hf_cfg = transformers.GPTNeoConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position_embeddings=64, window_size=4,
        attention_types=[[["global"], 2]])
    hf_model = transformers.GPTNeoForCausalLM(hf_cfg).eval()
    cfg = get_gpt_neo_config("test", vocab_size=128, hidden_size=32, num_hidden_layers=2,
                             num_attention_heads=4, intermediate_size=64,
                             max_position_embeddings=64, window_size=4)
    with pytest.raises(ValueError, match="attention_types"):
        load_hf_gpt_neo(hf_model, cfg)

    hf_cfg2 = transformers.GPTNeoConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position_embeddings=64, window_size=8,
        attention_types=[[["global", "local"], 1]])
    with pytest.raises(ValueError, match="window_size"):
        load_hf_gpt_neo(transformers.GPTNeoForCausalLM(hf_cfg2).eval(), cfg)
