"""T5 encoder-decoder: HF parity (relu and gated-gelu), decoder cache
equivalence, training through the engine with a seq2seq loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import T5ForConditionalGeneration, get_t5_config


def test_t5_forward_shapes():
    cfg = get_t5_config("test")
    m = T5ForConditionalGeneration(cfg)
    enc_ids = jnp.zeros((2, 12), jnp.int32)
    dec_ids = jnp.zeros((2, 6), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), enc_ids, dec_ids)["params"]
    logits = m.apply({"params": params}, enc_ids, dec_ids)
    assert logits.shape == (2, 6, cfg.vocab_size)


def test_t5_decode_matches_full_forward():
    cfg = get_t5_config("test")
    m = T5ForConditionalGeneration(cfg)
    rng = np.random.default_rng(0)
    enc_ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)
    dec_ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 7)), jnp.int32)
    # init, the full pass and the step jitted: eagerly each is dispatched an operation at a time
    params = jax.jit(m.init)(jax.random.PRNGKey(0), enc_ids, dec_ids)["params"]
    full = jax.jit(m.apply)({"params": params}, enc_ids, dec_ids)

    enc_out = m.apply({"params": params}, enc_ids, method=T5ForConditionalGeneration.encode)
    # incremental: one decoder token at a time against the cache
    variables = jax.jit(lambda key: m.init(key, enc_ids, dec_ids[:, :1], decode=True))(
        jax.random.PRNGKey(0))
    cache = jax.tree.map(jnp.zeros_like, variables["cache"])
    decode = jax.jit(lambda cache, token: m.apply(
        {"params": params, "cache": cache}, decoder_input_ids=token, encoder_outputs=enc_out,
        decode=True, mutable=["cache"]))
    outs = []
    for t in range(dec_ids.shape[1]):
        step, mut = decode(cache, dec_ids[:, t:t + 1])
        cache = mut["cache"]
        outs.append(step)
    decoded = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(full), atol=3e-4, rtol=3e-4)


def test_t5_trains_under_engine():
    cfg = get_t5_config("test")

    def seq2seq_loss(outputs, batch):
        from deepspeed_tpu.models.gpt2 import cross_entropy_loss
        return cross_entropy_loss(outputs, batch["labels"])

    class Wrapper(T5ForConditionalGeneration):
        def __call__(self, input_ids, *, deterministic=True, decoder_input_ids=None, **kw):
            return super().__call__(input_ids, decoder_input_ids=decoder_input_ids)

    engine, _, _, _ = deepspeed_tpu.initialize(model=Wrapper(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    }, loss_fn=seq2seq_loss)
    rng = np.random.default_rng(1)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32),
             "decoder_input_ids": rng.integers(0, cfg.vocab_size, (8, 8)).astype(np.int32),
             "labels": rng.integers(0, cfg.vocab_size, (8, 8)).astype(np.int32)}
    engine.initialize_state(batch)
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("variant", ["relu_tied", "gated_untied"])
def test_hf_t5_checkpoint_parity(variant):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from deepspeed_tpu.module_inject import load_hf_t5

    gated = variant == "gated_untied"
    hf_cfg = transformers.T5Config(vocab_size=128, d_model=32, d_kv=8, d_ff=64,
                                   num_layers=2, num_heads=4,
                                   feed_forward_proj="gated-gelu" if gated else "relu",
                                   tie_word_embeddings=not gated,
                                   dropout_rate=0.0)
    hf_model = transformers.T5ForConditionalGeneration(hf_cfg).eval()
    cfg = get_t5_config("test", vocab_size=128, d_model=32, d_kv=8, d_ff=64,
                        num_layers=2, num_heads=4,
                        feed_forward_proj="gated-gelu" if gated else "relu",
                        tie_word_embeddings=not gated)
    params = load_hf_t5(hf_model, cfg)
    rng = np.random.default_rng(2)
    enc_np = rng.integers(0, 128, (2, 9))
    dec_np = rng.integers(0, 128, (2, 5))
    with torch.no_grad():
        ref = hf_model(input_ids=torch.tensor(enc_np),
                       decoder_input_ids=torch.tensor(dec_np)).logits.numpy()
    ours = T5ForConditionalGeneration(cfg).apply(
        {"params": params}, jnp.asarray(enc_np, jnp.int32), jnp.asarray(dec_np, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), ref, atol=3e-4, rtol=3e-3)


def test_t5_init_cache_contract():
    """The zoo-wide init_cache helper must work for encoder-decoder models
    too (inference engine cache setup depends on it)."""
    from deepspeed_tpu.models.common import init_cache
    cfg = get_t5_config("test", max_cache_length=16)
    m = T5ForConditionalGeneration(cfg)
    cache = init_cache(m, batch_size=2)
    k = cache["decoder"]["block_0"]["SelfAttention"]["cached_key"]
    assert k.shape == (2, 16, cfg.num_heads, cfg.d_kv)
    assert float(jnp.abs(cache["decoder"]["block_0"]["SelfAttention"]["cache_index"])) == 0
