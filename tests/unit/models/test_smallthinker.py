"""SmallThinker held to its plain reference (``benchmarks/reference/smallthinker.py``)
on the CPU at the sizes of the ``smallthinker-test`` preset (8 layers, so two
periods of [full NoPE, window, window, window]; hidden 64; 4 query heads on 2
key heads of 32, so heads x head_dim is not the hidden size; 8 ReGLU experts
of 32, top 3, routed from the layer's input; window 8 at 32 positions):
logits, loss and every leaf's gradient, with every expert held and with a
share of them; the four shares of one layer add up to the uncut layer; the
model trains through ``deepspeed_tpu.initialize`` / ``engine.train_batch``.

Both sides are float32 here, so the router's choices agree wherever two
logits are not within 1e-6 of each other; the seeds below have no such tie.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness
from benchmarks.reference import smallthinker as ref
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config

family = harness.load_module(harness.REPO_ROOT, "benchmarks", "families", "smallthinker.py")

# weights are the package's initialisers at WEIGHT_SCALE times their 0.02, so
# that an expert's share of a logit is far above float32 rounding
WEIGHT_SCALE = 4.0
# float32 against float32, the same few hundred operations a value in another
# order, through eight layers
TOL = 2e-5
HELD = [None, (2, 4)]
IDS = ["whole", "held"]


def build(held=None, seed=0, **overrides):
    cfg = get_llama_config("smallthinker-test", moe_experts_held=held, **overrides)
    model = LlamaForCausalLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(jax.random.PRNGKey(seed),
                                               jnp.zeros((1, 32), jnp.int32))["params"])
    params = jax.tree.map(lambda p: p * WEIGHT_SCALE if p.ndim > 1 else p, params)
    return model, params


def spec_of(cfg):
    held = cfg.moe_experts_held or (0, cfg.moe_num_experts)
    return ref.Spec(top_k=cfg.moe_k, window=cfg.sliding_window,
                    windowed=cfg.sliding_window_layout, rotary=cfg.rope_layout,
                    theta=cfg.rope_theta, eps=cfg.rms_norm_eps, held_first=held[0])


def ids_of(seed, batch=2, length=32):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, (batch, length)), jnp.int32)


def package_loss(model, params, ids):
    logits, _ = model.apply({"params": params}, ids)
    return ref.nll(logits.astype(jnp.float32), ids)


@pytest.mark.parametrize("backend", ["xla", "flash"])
@pytest.mark.parametrize("held", HELD, ids=IDS)
def test_logits_and_loss_match_the_reference(held, backend):
    model, params = build(held, attention_backend=backend)
    ids = ids_of(1)
    spec = spec_of(model.config)
    # both sides jitted: eagerly each is dispatched an operation at a time
    logits, aux = jax.jit(model.apply)({"params": params}, ids)
    flat = family.to_reference(params)
    want = jax.jit(lambda flat: ref.forward(flat, ids, spec))(flat)
    assert float(aux) == 0.0    # moe_aux_loss_coef 0: cross-entropy alone
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(float(jax.jit(lambda p: package_loss(model, p, ids))(params)),
                               float(jax.jit(lambda flat: ref.loss(flat, ids, spec))(flat)),
                               rtol=1e-6)


@pytest.mark.parametrize("held", HELD, ids=IDS)
def test_every_leaf_of_the_gradient_matches_the_reference(held):
    """``jax.grad`` of the package's loss (flash kernels interpreted, the
    sorted or held route, its scatter-add combine) against ``jax.grad`` of the
    reference's, leaf by leaf under the family's names; the router's gradient
    flows through the six weights alone on both sides."""
    model, params = build(held, attention_backend="flash")
    ids = ids_of(2)
    spec = spec_of(model.config)
    # both jitted: eagerly each is traced and dispatched an operation at a time
    got = family.to_reference(jax.jit(jax.grad(lambda p: package_loss(model, p, ids)))(params))
    want = jax.jit(jax.grad(lambda flat: ref.loss(flat, ids, spec)))(family.to_reference(params))
    assert set(got) == set(want)
    for name in sorted(want):
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want[name]),
                                   atol=2e-4 * scale, err_msg=name)


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One layer's expert sum, computed a share of two experts at a time by
    the package's held route, added over the four shares, is the reference's
    sum over all eight (``ref.experts`` with every expert held): what one chip
    leaves out is exactly what the other three add."""
    from deepspeed_tpu.models.llama import LlamaMLP
    from deepspeed_tpu.moe import MoE

    cfg = get_llama_config("smallthinker-test")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 32, 64)), jnp.float32)      # the router's input
    n = jnp.asarray(rng.standard_normal((2, 32, 64)), jnp.float32)      # what the experts read
    whole = MoE(hidden_size=64, expert=LlamaMLP(cfg, num_experts=8), num_experts=8, k=3,
                drop_tokens=False, norm_topk_prob=True)
    params = nn.meta.unbox(whole.init(jax.random.PRNGKey(0), n, router_input=x)["params"])
    params = jax.tree.map(lambda p: p * WEIGHT_SCALE, params)
    moe = params["deepspeed_moe"]
    bank = moe["experts"]["deepspeed_experts"]
    bp = {"router": moe["gate"]["wg"], "gate": bank["gate_proj"]["kernel"],
          "up": bank["up_proj"]["kernel"], "down": bank["down_proj"]["kernel"]}
    want = ref.experts(bp, n, ref.router(bp, x, 3))

    total = jnp.zeros_like(n)
    for first in range(0, 8, 2):
        share = MoE(hidden_size=64, expert=LlamaMLP(cfg, num_experts=2), num_experts=8, k=3,
                    drop_tokens=False, norm_topk_prob=True, experts_held=(first, 2))
        mine = jax.tree.map(lambda p: p, params)
        mine["deepspeed_moe"]["experts"]["deepspeed_experts"] = jax.tree.map(
            lambda p: p[first:first + 2], bank)
        part, _, _ = share.apply({"params": mine}, n, router_input=x)
        # and the reference's own share is the same part
        held_bp = dict(bp, **{k: bp[k][first:first + 2] for k in ("gate", "up", "down")})
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(ref.experts(held_bp, n, ref.router(bp, x, 3), first)),
            atol=TOL)
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=TOL)
    # with no router input the gate reads what the experts read: another routing
    same, _, _ = whole.apply({"params": params}, n)
    assert float(jnp.max(jnp.abs(same - want))) > 1e-2


def test_the_router_reads_the_layers_input_not_the_normed_state():
    model, params = build()
    ids = ids_of(4)
    logits, _ = model.apply({"params": params}, ids)
    after = LlamaForCausalLM(get_llama_config("smallthinker-test",
                                              moe_router_before_attention=False))
    moved, _ = after.apply({"params": params}, ids)
    assert float(jnp.max(jnp.abs(logits - moved))) > 1e-2


def test_the_layouts_decide_window_and_rotation_a_layer_at_a_time():
    cfg = get_llama_config("smallthinker-test")
    assert [cfg.window_of(i) for i in range(8)] == [None, 8, 8, 8] * 2
    assert [cfg.rope_on(i) for i in range(8)] == [False, True, True, True] * 2
    assert cfg.head_dim == 32 and cfg.head_dim * cfg.num_attention_heads != cfg.hidden_size
    plain = get_llama_config("test", sliding_window=16)
    assert plain.head_dim == 16 and plain.window_of(1) == 16 and plain.rope_on(0)
    with pytest.raises(ValueError, match="names 3 layers"):
        get_llama_config("smallthinker-test", rope_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="moe_activation"):
        get_llama_config("smallthinker-test", moe_activation="gelu")


def test_decode_over_a_cache_longer_than_a_window_attends_the_window():
    """Where this raised by name ("keeps no ring") until PR 46: a window layer's
    decode walks its pool under the window's mask
    (``tests/unit/models/test_laguna.py`` holds it against the forward pass)."""
    model, params = build()
    out, upd = model.apply({"params": params}, ids_of(5, 1, 4), decode=True, mutable=["cache"])
    assert out[0].shape == (1, 4, 256)
    assert upd["cache"]["layers_1"]["self_attn"]["cached_key"].shape[1] == 32     # no ring asked for
    # a cache no longer than the window lies inside it: decode runs
    short = LlamaForCausalLM(get_llama_config("smallthinker-test", decode_cache_len=8))
    out, _ = short.apply({"params": params}, ids_of(5, 1, 4), decode=True, mutable=["cache"])
    assert out[0].shape == (1, 4, 256)


@pytest.mark.parametrize("held", HELD, ids=IDS)
def test_it_trains_through_initialize_and_train_batch(held):
    """The normal path: ``deepspeed_tpu.initialize`` -> ``engine.train_batch``
    in bf16 with remat, flash attention and the fused head loss, as the cell
    runs it; the first step's loss and gradient norm against the reference at
    the weights the engine holds, and the step's held-route counts."""
    import deepspeed_tpu
    from deepspeed_tpu.utils.trace import recorder

    cfg = get_llama_config("smallthinker-test", moe_experts_held=held, dtype=jnp.bfloat16,
                           attention_backend="flash", remat=True, fused_head_loss_chunk=32)
    from deepspeed_tpu.parallel.topology import MeshTopology
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), topology=MeshTopology(devices=jax.devices()[:1], data=1, fsdp=1),
        config={"train_batch_size": 2, "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 0}, "steps_per_print": 10 ** 9})
    batch = {"input_ids": np.asarray(ids_of(6))}
    engine.initialize_state(batch)
    flat = family.to_reference(engine.state.params)
    spec = spec_of(cfg)
    want_loss, grads = jax.jit(jax.value_and_grad(
        lambda flat: ref.loss(flat, batch["input_ids"], spec)))(flat)
    want_loss = float(want_loss)
    want_norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))))
    before = dict(recorder().counters)
    loss = float(engine.train_batch(batch))
    assert abs(loss - want_loss) / want_loss < 0.01
    assert abs(engine.get_global_grad_norm() - want_norm) / want_norm < 0.1
    # what this step added (the recorder is the process's: other tests' names stay)
    counted = {k: v - before.get(k, 0) for k, v in recorder().counters.items()
               if k.startswith("moe_") and v != before.get(k, 0)}
    if held is None:
        assert not counted
    else:
        copies = 2 * 32 * 3 * 8     # sequences x positions x top 3 x layers
        assert set(counted) <= {"moe_" + n for n in engine.module.step_count_names()}
        assert counted["moe_copies"] == copies
        assert 0 < counted["moe_rows_routed"] < copies
        assert counted["moe_rows_visited"] >= counted["moe_rows_routed"]
        assert counted["moe_rows_buffered"] == copies       # under 1,024 copies: one buffer
    assert float(engine.train_batch(batch)) < loss

