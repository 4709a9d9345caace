"""OLMoE held to its plain reference (``benchmarks/reference/olmoe.py``)
on the CPU, at sizes of the ``olmoe-test`` preset: the package's forward
pass, the scheduler's chunked prefill and int8-KV decode, and the gate
alone. Logits are compared, never tokens.

Routing is a discontinuity. Where the reference's k-th and (k+1)-th router
probabilities lie within rounding of each other, a correct implementation
in lower precision may take the other expert, and the two outputs then
differ by an expert's whole contribution. So values are compared under the
package's *own* choice of experts (the reference is handed that choice and
weights it by its own probabilities), and the choice is checked apart:
every expert the package took has a reference probability within
``CHOICE_EPS`` of the reference's k-th largest.
"""

import dataclasses

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmarks.lib import harness
from benchmarks.reference import olmoe as ref
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config

family = harness.load_module(harness.REPO_ROOT, "benchmarks", "families", "olmoe.py")

# experts, experts per token: the preset's 8 / 2, and OLMoE's ratio at 16 / 8
ROUTINGS = [(8, 2), (16, 8)]
N_HEAD = 4

# Weights are the package's initialisers at WEIGHT_SCALE times their 0.02:
# at 0.02 an expert's share of a logit (0.005) is what bf16 rounds away
# (0.004), and no tolerance could tell an altered reference. At 0.08 the
# logits spread over +-2 (std 0.64).
WEIGHT_SCALE = 4.0
# float32 against float32: both sides make the same few hundred operations
# a value in another order; 1e-6 was read, ten times that is allowed
TOL_F32 = 1e-5
# bfloat16 against float32 over the same (bf16-valued) weights: 8 mantissa
# bits, 2^-8 = 0.4% a rounding, through two layers: 0.020 was the most read
# over the seeds below, 0.03 is allowed. Leaving out the 8th expert, one
# expert's rows, the lack of renormalisation or the QK-norm moves a logit
# by 0.083, 0.083, 0.19 and 1.05 at the least (the four tests further down)
TOL_BF16 = 0.03
# a router probability the package computes from a bf16 hidden state is off
# by the rounding of the logits it is the softmax of: 1% of values of 0.05-0.3
CHOICE_EPS = {"float32": 1e-5, "bfloat16": 5e-3}


def build(experts, top_k, dtype, seed=0, **overrides):
    cfg = get_llama_config("olmoe-test", moe_num_experts=experts, moe_k=top_k, dtype=dtype,
                           **overrides)
    model = LlamaForCausalLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed),
                                               jnp.zeros((1, 8), jnp.int32))["params"])
    # the weights both sides see are the values the served type holds
    params = jax.tree.map(lambda p: (p * WEIGHT_SCALE if p.ndim > 1 else p).astype(dtype), params)
    return model, params


def ids_of(seed, batch=2, length=24):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, (batch, length)), jnp.int32)


def reference_forward(flat, ids, top_k, choices=None, alter=None):
    """``ref.forward`` put together from the reference's own pieces, so that
    a test can hand it the experts to take (``choices``: one [B, L, k] array
    a layer) or alter one piece. Returns (logits, router probabilities a
    layer)."""
    x, seen = ref.embed(flat, ids), []
    for i in range(ref.n_layers(flat)):
        bp = ref.block_params(flat, i)
        x = (attention_without_qk_norm if alter == "no_qk_norm" else ref.attention)(bp, x, N_HEAD)
        h = ref.rms_norm(x, jnp.asarray(bp["ln_ffn"], jnp.float32))
        with jax.default_matmul_precision("highest"):
            probs = jax.nn.softmax(h @ jnp.asarray(bp["router"], jnp.float32), axis=-1)
        seen.append(probs)
        if choices is not None:
            weights = jnp.sum(jax.nn.one_hot(choices[i], probs.shape[-1]), axis=-2) * probs
        else:
            weights = ref.router(bp, h, top_k - 1 if alter == "top_k_less_one" else top_k)
        if alter == "renormalised":
            weights = weights / weights.sum(-1, keepdims=True)
        if alter == "one_expert_dropped":
            weights = weights.at[..., 0].set(0.0)
        x = x + ref.experts(bp, h, weights)
    return ref.head(flat, x), seen


def attention_without_qk_norm(bp, x, n_head):
    """``ref.attention`` with its second and third ``rms_norm`` calls (the
    norms of q and of k; the first is the block's own) left out."""
    original, calls = ref.rms_norm, []

    def rms_norm(t, w):
        calls.append(1)
        return t if len(calls) in (2, 3) else original(t, w)

    ref.rms_norm = rms_norm
    try:
        return ref.attention(bp, x, n_head)
    finally:
        ref.rms_norm = original


def package_forward(model, params, ids):
    """(logits as float32, the experts each token took, a layer)."""
    (logits, _), state = jax.jit(lambda p: model.apply({"params": p}, ids,
                                                       mutable=["intermediates"]))(params)
    layers = sorted(k for k in state["intermediates"] if k.startswith("layers_"))
    choices = [state["intermediates"][k]["moe"]["deepspeed_moe"]["expert_choice"][0]
               .reshape(ids.shape + (-1,)) for k in layers]
    return np.asarray(logits, np.float32), choices


def check_choice(choices, probs, top_k, eps):
    """Every expert the package took is, by the reference's probabilities,
    within ``eps`` of the reference's k-th largest."""
    for taken, p in zip(choices, probs):
        kth = np.sort(np.asarray(p), axis=-1)[..., -top_k]
        took = np.take_along_axis(np.asarray(p), np.asarray(taken), axis=-1)
        assert (took >= kth[..., None] - eps).all(), float((kth[..., None] - took).max())
        assert len({tuple(row) for row in np.sort(np.asarray(taken), -1).reshape(-1, top_k)}) > 1


def test_the_recomposed_reference_is_the_reference():
    _, params = build(8, 2, jnp.float32)
    flat, ids = family.to_reference(params), ids_of(3)
    np.testing.assert_array_equal(np.asarray(reference_forward(flat, ids, 2)[0]),
                                  np.asarray(ref.forward(flat, ids, N_HEAD, 2)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("experts,top_k", ROUTINGS)
def test_package_forward_matches_the_reference(experts, top_k, dtype):
    name = jnp.dtype(dtype).name
    tol = TOL_F32 if dtype == jnp.float32 else TOL_BF16
    for seed in range(3):
        model, params = build(experts, top_k, dtype, seed)
        ids = ids_of(10 + seed)
        logits, choices = package_forward(model, params, ids)
        flat = family.to_reference(params)
        expected, probs = reference_forward(flat, ids, top_k, choices=choices)
        assert np.abs(logits - np.asarray(expected)).max() <= tol
        check_choice(choices, probs, top_k, CHOICE_EPS[name])
        if dtype == jnp.float32:
            # in float32 the choice is the reference's own and no hand-over is needed
            free = ref.forward(flat, ids, N_HEAD, top_k)
            assert np.abs(logits - np.asarray(free)).max() <= tol


@pytest.mark.parametrize("alter", ["top_k_less_one", "renormalised", "no_qk_norm",
                                   "one_expert_dropped"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_an_altered_reference_fails_the_comparison(alter, dtype):
    """The tolerances above are tight enough to tell the mathematics: a
    reference that takes 7 experts of 8, renormalises the weights, leaves
    out the QK-norm or drops one expert's rows is further from the package
    than the tolerance, in both types."""
    model, params = build(16, 8, dtype, seed=1)
    ids = ids_of(21)
    logits, choices = package_forward(model, params, ids)
    flat = family.to_reference(params)
    handed = None if alter == "top_k_less_one" else choices
    altered, _ = reference_forward(flat, ids, 8, choices=handed, alter=alter)
    tol = TOL_F32 if dtype == jnp.float32 else TOL_BF16
    assert np.abs(logits - np.asarray(altered)).max() > 2 * tol


def test_the_bank_takes_both_kernels_and_both_layouts():
    """Grouped rows through ``jax.lax.ragged_dot`` and through the megablox
    kernel (interpreted here), and the capacity layout of the same bank,
    give one result."""
    model, params = build(8, 2, jnp.float32)
    ids = ids_of(5)
    base = np.asarray(model.apply({"params": params}, ids)[0])
    for kernel in ("xla", "pallas"):
        cfg = dataclasses.replace(model.config, moe_route_kernel=kernel)
        out = np.asarray(LlamaForCausalLM(cfg).apply({"params": params}, ids)[0])
        np.testing.assert_allclose(out, base, atol=2e-5)
    dense = LlamaForCausalLM(dataclasses.replace(model.config, moe_route="dense"))
    np.testing.assert_allclose(np.asarray(dense.apply({"params": params}, ids)[0]), base, atol=2e-5)


# --------------------------------------------------------------------------
# the gate alone
# --------------------------------------------------------------------------
def gate_on(logits, k, **kw):
    from deepspeed_tpu.moe.sharded_moe import topkrouting
    return topkrouting(jnp.asarray(logits), k, 1.0, 4, **kw)


def test_gate_takes_the_top_k_of_the_fp32_softmax_with_its_values_as_weights():
    logits = np.random.default_rng(0).normal(size=(40, 64)).astype(np.float32)
    _, routing, _ = gate_on(logits, 8, drop_tokens=False, normalize=False)
    values, chosen = jax.lax.top_k(jax.nn.softmax(jnp.asarray(logits), axis=-1), 8)
    np.testing.assert_array_equal(np.asarray(routing.expert), np.asarray(chosen))
    np.testing.assert_array_equal(np.asarray(routing.weight), np.asarray(values))
    assert np.asarray(routing.weight).sum(-1).max() < 1.0      # not renormalised
    assert (np.asarray(routing.keep) == 1).all()
    _, normed, _ = gate_on(logits, 8, drop_tokens=False, normalize=True)
    np.testing.assert_allclose(np.asarray(normed.weight).sum(-1), 1.0, rtol=1e-6)


def test_no_token_is_dropped_when_every_token_wants_the_same_experts():
    """All-to-one skew: 40 tokens, the same 8 experts each. Every copy is
    kept, and inside an expert every copy has a row of its own."""
    logits = np.tile(np.linspace(0.0, 6.3, 64, dtype=np.float32), (40, 1))
    _, routing, exp_counts = gate_on(logits, 8, drop_tokens=False)
    assert (np.asarray(routing.keep) == 1).all()
    assert set(np.asarray(routing.expert).reshape(-1)) == set(range(56, 64))
    rows = set(zip(np.asarray(routing.expert).reshape(-1), np.asarray(routing.slot).reshape(-1)))
    assert len(rows) == 40 * 8 and np.asarray(routing.slot).max() == 39
    assert int(exp_counts[63]) == 40
    # with a bounded capacity the same skew drops what overflows, lowest rank first
    _, bounded, _ = gate_on(logits, 8, drop_tokens=True)
    kept = np.asarray(bounded.keep)
    assert kept.sum() < kept.size and (kept[:, 0] >= kept[:, -1]).all()


def test_the_whole_layer_drops_no_token_under_the_same_skew():
    """The drop-free layer against every token through its 8 experts by
    hand, with a router that sends all tokens one way."""
    from deepspeed_tpu.models.llama import LlamaMLP
    from deepspeed_tpu.moe import MoE

    cfg = get_llama_config("olmoe-test", moe_num_experts=16, moe_k=8)
    layer = MoE(hidden_size=64, expert=LlamaMLP(cfg, num_experts=16), num_experts=16, k=8,
                drop_tokens=False, norm_topk_prob=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64))
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(1), x)["params"])
    skew = jnp.zeros((64, 16)).at[0, :].set(jnp.arange(16.0))       # only feature 0 routes
    params["deepspeed_moe"]["gate"]["wg"] = skew
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)                   # and always the same way
    out, _, exp_counts = layer.apply({"params": params}, x)
    assert int(exp_counts[15]) == 48
    bank = params["deepspeed_moe"]["experts"]["deepspeed_experts"]
    probs = jax.nn.softmax(x @ skew, axis=-1)
    want = 0.0
    for e in range(8, 16):
        y = (jax.nn.silu(x @ bank["gate_proj"]["kernel"][e]) * (x @ bank["up_proj"]["kernel"][e])
             ) @ bank["down_proj"]["kernel"][e]
        want = want + y * probs[..., e:e + 1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_top1_and_top2_gates_are_what_they_were(k):
    """``TopKGate`` with k in {1, 2} still runs the reference cores (top-2
    renormalised, its second choice behind every first)."""
    from deepspeed_tpu.moe.sharded_moe import TopKGate, top1routing, top2routing

    tokens = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 16))
    gate = TopKGate(16, 8, k, capacity_factor=1.0, eval_capacity_factor=1.0, min_capacity=4,
                    route="sorted")
    variables = gate.init(jax.random.PRNGKey(3), tokens)
    _, routing, _ = gate.apply(variables, tokens)
    logits = jnp.einsum("gsm,me->gse", tokens, nn.meta.unbox(variables["params"])["wg"])[0]
    core = (top1routing(logits, 1.0, 4, None, None, True, True, None) if k == 1
            else top2routing(logits, 1.0, 4, True, None))[1]
    for got, want in zip(routing, core):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))


def test_any_k_up_to_the_experts_is_a_gate_and_more_is_an_error():
    from deepspeed_tpu.moe.sharded_moe import TopKGate

    tokens = jnp.ones((1, 8, 16))
    for k in (3, 8):
        gate = TopKGate(16, 8, k, route="sorted")
        _, routing, _ = gate.apply(gate.init(jax.random.PRNGKey(0), tokens), tokens)
        assert routing.expert.shape == (1, 8, k)
    with pytest.raises(ValueError, match="1 <= k <= experts"):
        TopKGate(16, 8, 9, route="sorted").init(jax.random.PRNGKey(0), tokens)


# --------------------------------------------------------------------------
# through the scheduler
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 0.02), (jnp.bfloat16, 0.06)],
                         ids=["float32", "bfloat16"])
def test_scheduler_prefill_and_int8_decode_match_the_reference(dtype, tol):
    """Chunked prefill, then decode over the int8 per-slot cache, with more
    requests than slots so that slots are joined and left: every emitted
    token's reference logit lies within ``tol`` of the reference's largest
    at its position (the reference's full forward pass over prompt and
    output). An int8 cache element is off by up to 1/254 of its row's
    largest, which moves logits that spread over +-0.5 by under 0.01; bf16
    adds its roundings and, rarely, another expert. A token from a wrong
    position or slot lands 0.3 and more below."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler, Request,
                                                 ServingConfig)

    model, params = build(16, 8, dtype, decode_cache_len=64)
    engine = deepspeed_tpu.init_inference(model, params=params, dtype=dtype,
                                          replace_with_kernel_inject=True, max_out_tokens=64)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=4, page_size=16, kv_quant=True, prefill_chunk=16, prefill_interleave=1,
        prefix_cache="off"))
    assert sched.capacity == 64
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=rng.integers(0, 256, (n,)).astype(np.int32), max_new_tokens=m)
            for n, m in ((20, 6), (33, 9), (7, 12), (40, 5), (12, 8), (18, 7), (50, 10))]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    # one reference pass over the seven, padded on the right to the longest:
    # the reference is causal and drops no token, so a row's logits up to its
    # length are its own
    fed = [np.concatenate([r.prompt, np.asarray(r.output, np.int32)])[:-1] for r in reqs]
    ids = np.stack([np.pad(row, (0, max(map(len, fed)) - len(row))) for row in fed])
    every = np.asarray(ref.forward(family.to_reference(engine.params), jnp.asarray(ids), N_HEAD, 8))
    worst = 0.0
    for r, row, logits in zip(reqs, fed, every):
        assert len(r.output) == r.max_new_tokens
        logits = logits[len(r.prompt) - 1:len(row)]
        gap = logits.max(-1) - logits[np.arange(len(r.output)), np.asarray(r.output)]
        worst = max(worst, float(gap.max()))
    assert worst <= tol, worst
    from deepspeed_tpu.utils import trace
    counters = trace.recorder().counters
    assert counters["moe_rows_computed"] >= counters["moe_rows_routed"] > 0


def test_moe_rows_count_what_the_expert_matmuls_are_given():
    model, _ = build(16, 8, jnp.float32)
    # two expert layers: a position owes 2 x 8 rows; grouped, 64 positions are 64 x 8 x 2 rows
    assert model.moe_rows(64) == (16, 1024)
    capped = LlamaForCausalLM(get_llama_config("mixtral-test"))
    per_position, rows = capped.moe_rows(64)
    # Mixtral's capacity layout: 4 experts x capacity(64 tokens, factor 2.0 x 2) x 2 layers
    assert per_position == 4 and rows == 4 * 64 * 2
    assert LlamaForCausalLM(get_llama_config("test")).moe_rows(64) == (0, 0)
