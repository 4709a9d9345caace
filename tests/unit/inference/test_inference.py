"""Inference tests (reference ``tests/unit/inference/test_inference.py``):
engine generate correctness, TP sharding, AutoTP, HF checkpoint parity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import (GPT2LMHeadModel, LlamaForCausalLM, get_gpt2_config, get_llama_config)
from deepspeed_tpu.module_inject import AutoTP, load_hf_gpt2
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


def test_gpt2_decode_cache_matches_full_forward():
    cfg = get_gpt2_config("test")
    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    # init, the full pass and the step jitted: eagerly each is dispatched an operation at a time
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids)
    full = jax.jit(model.apply)(variables, ids)
    decode = jax.jit(lambda cache, fed: model.apply({**variables, **cache}, fed, decode=True,
                                                    mutable=["cache"]))

    from deepspeed_tpu.models.common import init_cache
    cache = {"cache": init_cache(model, batch_size=2)}
    out, cache = decode(cache, ids[:, :8])
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, :8]), rtol=2e-4, atol=2e-4)
    for t in range(8, 12):
        out, cache = decode(cache, ids[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, t]), rtol=2e-4, atol=2e-4)


def test_generate_greedy_matches_manual_loop():
    cfg = get_llama_config("test")
    model = LlamaForCausalLM(cfg)
    engine = deepspeed_tpu.init_inference(model, mp_size=2)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    out = engine.generate(prompt, max_new_tokens=6)
    assert out.shape == (2, 14)

    # the manual greedy loop over the full forward (no cache) must agree. The
    # model is causal, so one forward over the output holds every step of
    # that loop: token t + 1 is the argmax at t, given the tokens up to t
    np.testing.assert_array_equal(np.asarray(out)[:, :8], prompt)
    logits = jax.jit(lambda p, ids: model.apply({"params": p}, ids))(engine.params, out[:, :-1])
    np.testing.assert_array_equal(np.asarray(out)[:, 8:], np.asarray(jnp.argmax(logits[:, 7:], axis=-1)))


def test_generate_eos_early_stop():
    cfg = get_llama_config("test")
    model = LlamaForCausalLM(cfg)
    engine = deepspeed_tpu.init_inference(model)
    prompt = np.zeros((1, 4), np.int32)
    full = engine.generate(prompt, max_new_tokens=8)
    greedy_first = int(np.asarray(full)[0, 4])
    out = engine.generate(prompt, max_new_tokens=8, eos_token_id=greedy_first)
    # first generated token is EOS → generation stops immediately
    assert out.shape[1] <= 4 + 2


def test_generate_sampling_seeded():
    cfg = get_llama_config("test")
    engine = deepspeed_tpu.init_inference(LlamaForCausalLM(cfg))
    prompt = np.zeros((1, 4), np.int32)
    a = engine.generate(prompt, max_new_tokens=5, do_sample=True, temperature=0.8, top_k=20,
                        rng=jax.random.PRNGKey(7))
    b = engine.generate(prompt, max_new_tokens=5, do_sample=True, temperature=0.8, top_k=20,
                        rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tp_sharding_applied():
    cfg = get_llama_config("test")
    engine = deepspeed_tpu.init_inference(LlamaForCausalLM(cfg), mp_size=4,
                                          dtype="bfloat16")
    k = engine.params["layers_0"]["mlp"]["gate_proj"]["kernel"]
    assert k.dtype == jnp.bfloat16
    assert "tensor" in jax.tree.leaves(tuple(k.sharding.spec)), k.sharding.spec
    # logits still correct under TP: compare against unsharded fp32 engine
    e32 = deepspeed_tpu.init_inference(LlamaForCausalLM(cfg))
    prompt = np.zeros((1, 8), np.int32)
    # different random inits → just check it runs and shapes match
    assert engine.forward(prompt).shape == e32.forward(prompt).shape


def test_autotp_heuristics():
    params = {
        "h_0": {"attn": {"q_proj": {"kernel": np.zeros((64, 64)), "bias": np.zeros((64,))},
                         "o_proj": {"kernel": np.zeros((64, 64))}},
                "mlp": {"up_proj": {"kernel": np.zeros((64, 256))},
                        "down_proj": {"kernel": np.zeros((256, 64))}}},
        "ln": {"scale": np.zeros((64,))},
        "embed_tokens": np.zeros((256, 64)),
    }
    specs = AutoTP.tp_parser(params, tp_size=4)
    from jax.sharding import PartitionSpec as P
    assert specs["h_0"]["attn"]["q_proj"]["kernel"] == P(None, "tensor")  # column
    assert specs["h_0"]["attn"]["q_proj"]["bias"] == P("tensor")
    assert specs["h_0"]["attn"]["o_proj"]["kernel"] == P("tensor", None)  # row
    assert specs["h_0"]["mlp"]["down_proj"]["kernel"] == P("tensor", None)
    assert specs["ln"]["scale"] == P()
    assert specs["embed_tokens"] == P("tensor")


def test_autotp_shape_heuristic_for_unknown_names():
    """Unknown naming conventions: non-square 2-D kernels classify by aspect
    ratio (fused-QKV / gated-MLP are expanding, down-projections contracting);
    square kernels stay replicated."""
    params = {
        "blk": {"proj_in_weird": {"kernel": np.zeros((64, 192))},   # d -> 3d
                "proj_out_weird": {"kernel": np.zeros((256, 64))},  # 4d -> d
                "mixer": {"kernel": np.zeros((64, 64))}},           # square: ambiguous
    }
    specs = AutoTP.tp_parser(params, tp_size=4)
    from jax.sharding import PartitionSpec as P
    assert specs["blk"]["proj_in_weird"]["kernel"] == P(None, "tensor")
    assert specs["blk"]["proj_out_weird"]["kernel"] == P("tensor", None)
    assert specs["blk"]["mixer"]["kernel"] == P()


def test_hf_gpt2_checkpoint_parity():
    """HF torch GPT-2 logits == converted deepspeed_tpu logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
                                     resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    hf_model = transformers.GPT2LMHeadModel(hf_cfg).eval()

    cfg = get_gpt2_config("test", vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)
    params = load_hf_gpt2(hf_model, cfg)

    ids = np.random.default_rng(0).integers(0, 128, (2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = GPT2LMHeadModel(cfg).apply({"params": params}, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_inference_config_parity():
    from deepspeed_tpu.inference import DeepSpeedInferenceConfig
    c = DeepSpeedInferenceConfig(dtype="float16", tensor_parallel={"tp_size": 8},
                                 replace_with_kernel_inject=True, enable_cuda_graph=True,
                                 max_out_tokens=2048)
    assert c.dtype == jnp.float16
    assert c.tensor_parallel.tp_size == 8
    assert c.max_tokens == 2048
    with pytest.raises(ValueError):
        DeepSpeedInferenceConfig(dtype="float13")


def test_moe_model_generates():
    """MoE inference (reference ops/transformer/inference/moe_inference.py +
    InferenceEngine EP groups): an expert-parallel GPT-2 serves through
    init_inference with deterministic eval-mode gating."""
    cfg = get_gpt2_config("test", moe_num_experts=4, moe_layer_freq=2, moe_k=1)
    model = GPT2LMHeadModel(cfg)
    ids = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(ids),
                           deterministic=True)
    engine = deepspeed_tpu.init_inference(model, config={"dtype": "fp32"},
                                          params=variables["params"])
    out = engine.generate(ids, max_new_tokens=4)
    assert out.shape == (2, 12)
    assert (np.asarray(out[:, :8]) == ids).all()
    assert np.isfinite(np.asarray(out)).all()
    # same prompt twice -> same greedy output (deterministic gating at eval)
    out2 = engine.generate(ids, max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_moe_model_forward_returns_logits():
    """engine(ids) must return plain logits for MoE models too (the aux
    loss is a training regularizer, not a serving output)."""
    cfg = get_gpt2_config("test", moe_num_experts=4, moe_layer_freq=2)
    model = GPT2LMHeadModel(cfg)
    ids = np.zeros((1, 8), np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(ids))
    engine = deepspeed_tpu.init_inference(model, config={"dtype": "fp32"},
                                          params=variables["params"])
    out = engine(ids)
    assert not isinstance(out, tuple)
    assert out.shape == (1, 8, cfg.vocab_size)


class TestBeamSearch:

    def _engine(self):
        cfg = get_gpt2_config("test")
        model = GPT2LMHeadModel(cfg)
        ids = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
        variables = model.init(jax.random.PRNGKey(0), jnp.asarray(ids))
        return deepspeed_tpu.init_inference(model, config={"dtype": "fp32"},
                                            params=variables["params"]), ids, cfg

    def test_one_beam_equals_greedy(self):
        engine, ids, _ = self._engine()
        greedy = engine.generate(ids, max_new_tokens=5)
        # num_beams=1 must route through the greedy path (identical output)
        one = engine.generate(ids, max_new_tokens=5, num_beams=1)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(greedy))
        # beams=2 must score at least as well as greedy under summed logprob
        beam = engine.generate(ids, max_new_tokens=5, num_beams=2, length_penalty=0.0)
        assert beam.shape == greedy.shape

        # score both continuations under the model: beam >= greedy
        def seq_logprob(full):
            logits = np.asarray(jax.device_get(engine(np.asarray(full))), np.float32)
            lp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
            total = []
            for b in range(full.shape[0]):
                s = 0.0
                for t in range(ids.shape[1] - 1, full.shape[1] - 1):
                    s += float(lp[b, t, int(full[b, t + 1])])
                total.append(s)
            return np.asarray(total)

        g, bm = seq_logprob(np.asarray(greedy)), seq_logprob(np.asarray(beam))
        assert (bm >= g - 1e-4).all(), (bm, g)

    def test_beam_prompt_preserved_and_deterministic(self):
        engine, ids, _ = self._engine()
        out1 = engine.generate(ids, max_new_tokens=4, num_beams=3)
        out2 = engine.generate(ids, max_new_tokens=4, num_beams=3)
        assert out1.shape == (2, 12)
        assert (np.asarray(out1[:, :8]) == ids).all()
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_beam_rejects_sampling(self):
        engine, ids, _ = self._engine()
        with pytest.raises(ValueError):
            engine.generate(ids, max_new_tokens=2, num_beams=2, do_sample=True)

    def test_beam_eos_early_stop(self):
        """Force a guaranteed-immediate EOS: use each row's greedy next token
        as the eos id for a 1-row batch, so every beam finishes at step 1 and
        the loop must stop early (output narrower than prompt+max_new)."""
        engine, ids, _ = self._engine()
        row = ids[:1]
        greedy = engine.generate(row, max_new_tokens=1)
        eos = int(np.asarray(greedy)[0, -1])
        out = engine.generate(row, max_new_tokens=6, num_beams=1, eos_token_id=eos)
        assert out.shape[1] < row.shape[1] + 6, out.shape
        # beam path: once eos appears in the best hypothesis, every later
        # position is the eos fill
        bout = np.asarray(engine.generate(row, max_new_tokens=6, num_beams=2,
                                          eos_token_id=eos))
        assert bout.shape[1] <= row.shape[1] + 6 and np.isfinite(bout).all()
        gen = bout[0, row.shape[1]:]
        if eos in gen:
            first = int(np.argmax(gen == eos))
            assert (gen[first:] == eos).all(), gen


def test_autotp_injection_policy_overrides():
    """injection_policy (reference init_inference(injection_policy=...))
    overrides name classification: reference-form tuples mark row-parallel
    projections; explicit role strings force any layout."""
    params = {
        "blk": {"mixer": {"kernel": np.zeros((64, 64))},       # ambiguous square
                "q_proj": {"kernel": np.zeros((64, 64))}},     # name says column
    }
    from jax.sharding import PartitionSpec as P
    # reference form: tuple of names that need the output all-reduce (row)
    specs = AutoTP.tp_parser(params, tp_size=4, policy={"SomeLayer": ("mixer",)})
    assert specs["blk"]["mixer"]["kernel"] == P("tensor", None)
    assert specs["blk"]["q_proj"]["kernel"] == P(None, "tensor")  # untouched
    # explicit role form, overriding the built-in name vocabulary
    specs = AutoTP.tp_parser(params, tp_size=4,
                             policy={"q_proj": "replicate", "mixer": "column"})
    assert specs["blk"]["q_proj"]["kernel"] == P()
    assert specs["blk"]["mixer"]["kernel"] == P(None, "tensor")
    with pytest.raises(ValueError):
        AutoTP.normalize_policy({"x": "diagonal"})


def test_injection_policy_reaches_serving_engine():
    """init_inference(..., injection_policy=...) must change the served
    weight layout (the config field used to be accepted and ignored)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test")
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    import flax.linen as fnn
    params = fnn.meta.unbox(params)
    engine = deepspeed_tpu.init_inference(
        model, params=params, mp_size=4, replace_with_kernel_inject=False,
        injection_policy={"h_0/attn/c_attn": "replicate"})
    from jax.sharding import PartitionSpec as P
    spec = engine.param_specs["h_0"]["attn"]["c_attn"]["kernel"]
    assert all(p is None for p in spec), spec  # replicated
    # sibling layers keep their annotated/classified TP layout
    flat = jax.tree.leaves(engine.param_specs, is_leaf=lambda x: isinstance(x, P))
    assert any(s != P() for s in flat)


def test_injection_policy_specificity_and_unmatched_warning(caplog):
    """Longest substring wins (specific overrides general); rules matching
    no path warn instead of failing open silently."""
    import logging
    params = {"blk": {"attn": {"c_attn": {"kernel": np.zeros((64, 192))},
                               "c_proj": {"kernel": np.zeros((64, 64))}}}}
    from jax.sharding import PartitionSpec as P
    specs = AutoTP.tp_parser(params, tp_size=4,
                             policy={"attn": "row", "attn/c_attn": "column"})
    assert specs["blk"]["attn"]["c_attn"]["kernel"] == P(None, "tensor")  # specific
    assert specs["blk"]["attn"]["c_proj"]["kernel"] == P("tensor", None)  # general
    from deepspeed_tpu.utils.logging import logger as ds_logger
    ds_logger.addHandler(caplog.handler)  # ds logger has propagate=False
    try:
        with caplog.at_level(logging.WARNING):
            AutoTP.tp_parser(params, tp_size=4,
                             policy={"transformer.h.0.attn.c_proj": "replicate"})
    finally:
        ds_logger.removeHandler(caplog.handler)
    assert any("matched no" in r.getMessage() for r in caplog.records)


def test_replace_policy_classes_drive_tp_rules():
    """replace_policy policy classes (reference replace_policy.py surface)
    expand to TP role rules when passed as injection_policy values."""
    from deepspeed_tpu.module_inject import (HFGPT2LayerPolicy, generic_policies,
                                             replace_policies)
    assert len(replace_policies) == 11 and len(generic_policies) == 2
    params = {"h_0": {"attn": {"c_attn": {"kernel": np.zeros((64, 192))},
                               "c_proj": {"kernel": np.zeros((64, 64))}}}}
    from jax.sharding import PartitionSpec as P
    specs = AutoTP.tp_parser(params, tp_size=4,
                             policy={"GPT2Block": HFGPT2LayerPolicy})
    assert specs["h_0"]["attn"]["c_attn"]["kernel"] == P(None, "tensor")
    assert specs["h_0"]["attn"]["c_proj"]["kernel"] == P("tensor", None)


def test_policy_single_token_rules_match_parts_not_substrings():
    """Single-token policy rules must match whole path parts; raw substring
    containment would let 'value' capture 'value_head'/'key_value_cache'."""
    params = {"blk": {"value": {"kernel": np.zeros((64, 64))},
                      "value_head": {"kernel": np.zeros((64, 64))},
                      "my_cache_of_values": {"kernel": np.zeros((64, 64))}}}
    from jax.sharding import PartitionSpec as P
    specs = AutoTP.tp_parser(params, tp_size=4, policy={"value": "column"})
    assert specs["blk"]["value"]["kernel"] == P(None, "tensor")
    assert specs["blk"]["my_cache_of_values"]["kernel"] == P()
