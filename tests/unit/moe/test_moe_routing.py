"""Sorted-route MoE tests: the route as a field of the layer and the model
configuration, dense-vs-sorted parity (fwd outputs + grads) across the
top1/top2 × drop/no-drop × deterministic/RTS matrix, the no-[G,S,E,C]
jaxpr guarantee, and a sharded EP>=2 dryrun with ``route=sorted``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flax.linen as nn

import deepspeed_tpu
from deepspeed_tpu.moe.sharded_moe import MOELayer, _capacity, top1gating, top1routing, top2gating, top2routing
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.fixture(autouse=True)
def _clean():
    set_topology(None)
    yield
    set_topology(None)


# ---------------------------------------------------------------------------
# the route is a field: of the layer, of the model config, of the "moe" block
# ---------------------------------------------------------------------------
def _moe_block_config(**moe):
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    return DeepSpeedConfig({"train_batch_size": 8, **({"moe": moe} if moe else {})},
                           dp_world_size=1)


def test_route_resolution_layers():
    """Default sorted/auto on the layer and on the model configuration; the
    engine's "moe" block lands on a copy of the model configuration and on
    nothing else."""
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.runtime.engine import _apply_program_knobs

    layer = MOELayer(expert=_TinyExpert(), model_dim=8, num_experts=4)
    assert (layer.route, layer.route_kernel) == ("sorted", "auto")
    cfg = get_gpt2_config("test", moe_num_experts=4)
    assert (cfg.moe_route, cfg.moe_route_kernel) == ("sorted", "auto")
    assert get_gpt2_config("test", moe_route="dense").moe_route == "dense"

    model = GPT2LMHeadModel(cfg)
    assert _apply_program_knobs(model, _moe_block_config()) is model
    built = _apply_program_knobs(model, _moe_block_config(route="dense", kernel="xla"))
    assert (built.config.moe_route, built.config.moe_route_kernel) == ("dense", "xla")
    assert (model.config.moe_route, model.config.moe_route_kernel) == ("sorted", "auto")
    assert _apply_program_knobs(model, _moe_block_config(kernel="pallas")).config.moe_route == "sorted"


def test_route_resolution_validates():
    x = jnp.zeros((2, 8, 8), jnp.float32)

    def init(**kw):
        MOELayer(expert=_TinyExpert(), model_dim=8, num_experts=4, **kw).init(
            jax.random.PRNGKey(0), x)

    with pytest.raises(ValueError, match="route"):
        init(route="einsum")
    with pytest.raises(ValueError, match="kernel"):
        init(route_kernel="cuda")
    with pytest.raises(ValueError, match="route"):
        _moe_block_config(route="blocksparse")


# ---------------------------------------------------------------------------
# gating: compact routing mirrors the dense tensors exactly
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_rts", [False, True])
def test_top1routing_matches_top1gating(use_rts):
    S, E, cf = 32, 4, 1.0
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(S, E)), jnp.float32)
    rng = jax.random.PRNGKey(3) if use_rts else None
    l_d, combine, dispatch, counts_d = top1gating(logits, cf, 1, use_rts=use_rts, rng=rng)
    l_s, rt, counts_s = top1routing(logits, cf, 1, use_rts=use_rts, rng=rng)
    np.testing.assert_allclose(float(l_d), float(l_s))
    np.testing.assert_array_equal(np.asarray(counts_d), np.asarray(counts_s))
    capacity = _capacity(S, E, cf, 1)
    # rebuild the dense tensors from the compact fields: must be identical
    rebuilt = np.zeros((S, E, capacity), np.float32)
    rt_np = {f: np.asarray(v) for f, v in rt._asdict().items()}
    for s in range(S):
        if rt_np["keep"][s, 0]:
            rebuilt[s, rt_np["expert"][s, 0], rt_np["slot"][s, 0]] = rt_np["weight"][s, 0]
    np.testing.assert_allclose(rebuilt, np.asarray(combine))
    np.testing.assert_array_equal(rebuilt > 0, np.asarray(dispatch))


def test_top2routing_matches_top2gating():
    S, E = 32, 4
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(S, E)), jnp.float32)
    rng = jax.random.PRNGKey(5)
    l_d, combine, dispatch, counts_d = top2gating(logits, 1.0, 1, rng=rng)
    l_s, rt, counts_s = top2routing(logits, 1.0, 1, rng=rng)
    np.testing.assert_allclose(float(l_d), float(l_s))
    np.testing.assert_array_equal(np.asarray(counts_d), np.asarray(counts_s))
    capacity = _capacity(S, E, 2.0, 1)
    rebuilt = np.zeros((S, E, capacity), np.float32)
    rt_np = {f: np.asarray(v) for f, v in rt._asdict().items()}
    for s in range(S):
        for j in range(2):
            if rt_np["keep"][s, j]:
                rebuilt[s, rt_np["expert"][s, j], rt_np["slot"][s, j]] += rt_np["weight"][s, j]
    np.testing.assert_allclose(rebuilt, np.asarray(combine), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# layer parity: fwd + grads, full matrix
# ---------------------------------------------------------------------------
class _TinyExpert(nn.Module):

    @nn.compact
    def __call__(self, x, deterministic=True):
        return nn.Dense(x.shape[-1], use_bias=False,
                        kernel_init=nn.initializers.normal(1.0))(x)


def _run_layer(route, k, cf, deterministic, use_rts, kernel="auto", x=None):
    M, E = 8, 4
    layer = MOELayer(expert=_TinyExpert(), model_dim=M, num_experts=E, k=k,
                     capacity_factor=cf, eval_capacity_factor=cf, min_capacity=1,
                     use_rts=use_rts, route=route, route_kernel=kernel)
    variables = layer.init(jax.random.PRNGKey(0), x)

    def loss(v, xx):
        (out, l_aux, _), _ = layer.apply(
            v, xx, deterministic=deterministic, mutable=["intermediates"],
            rngs=None if deterministic else {"gating": jax.random.PRNGKey(7)})
        return (out**2).sum() + l_aux, out

    # jitted: eagerly each backward is dispatched an operation at a time
    (lv, out), gv = jax.jit(jax.value_and_grad(loss, has_aux=True))(variables, x)
    gx = jax.jit(jax.grad(lambda xx: loss(variables, xx)[0]))(x)
    return lv, out, gv, gx


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("deterministic,use_rts", [(True, True), (False, True), (False, False)])
@pytest.mark.parametrize("cf", [0.25, 4.0])  # drop-heavy and no-drop regimes
def test_dense_sorted_parity_fwd_and_grads(k, deterministic, use_rts, cf):
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 8, 8)), jnp.float32)
    l_d, out_d, g_d, gx_d = _run_layer("dense", k, cf, deterministic, use_rts, x=x)
    l_s, out_s, g_s, gx_s = _run_layer("sorted", k, cf, deterministic, use_rts, x=x)
    np.testing.assert_allclose(float(l_d), float(l_s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_s), rtol=1e-6, atol=1e-7)
    # grads: identical dot products, different contraction order — fp32
    # reassociation noise only (same tolerance as the layer-vs-manual test)
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g_d),
                               jax.tree_util.tree_leaves_with_path(g_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
                                   err_msg=str(pa))
    np.testing.assert_allclose(np.asarray(gx_d), np.asarray(gx_s), rtol=2e-5, atol=2e-5)


def test_sorted_pallas_kernel_matches_xla_end_to_end():
    """route=sorted with the Pallas permutation kernel (interpret mode on
    CPU) is numerically identical to the XLA permutation — fwd and grads."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 8, 8)), jnp.float32)
    l_x, out_x, g_x, gx_x = _run_layer("sorted", 2, 1.0, True, True, kernel="xla", x=x)
    l_p, out_p, g_p, gx_p = _run_layer("sorted", 2, 1.0, True, True, kernel="pallas", x=x)
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_p))
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g_x),
                               jax.tree_util.tree_leaves_with_path(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, err_msg=str(pa))
    np.testing.assert_allclose(np.asarray(gx_x), np.asarray(gx_p), rtol=1e-6)


def test_sorted_route_sows_load_stats():
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 8, 8)), jnp.float32)
    layer = MOELayer(expert=_TinyExpert(), model_dim=8, num_experts=4, k=1,
                     capacity_factor=0.5, eval_capacity_factor=0.5, min_capacity=1,
                     route="sorted")
    variables = layer.init(jax.random.PRNGKey(0), x)
    (_, _, _), ivars = layer.apply(variables, x, mutable=["intermediates"])
    inter = ivars["intermediates"]
    exp_counts = np.asarray(inter["exp_counts"][0])
    kept = np.asarray(inter["kept_counts"][0])
    routed = np.asarray(inter["routed_counts"][0])
    slots = int(inter["capacity_slots"][0])
    assert exp_counts.sum() == 16  # every token routed
    np.testing.assert_array_equal(routed, exp_counts)  # k=1: same thing
    assert np.all(kept <= routed)  # drops only ever reduce
    assert kept.sum() <= slots * 4  # never over the buffer
    assert slots == 1 * _capacity(16, 4, 0.5, 1)  # groups=1 (no topology)


@pytest.mark.parametrize("cf", [0.25, 8.0])
def test_top2_drop_fraction_is_sane(cf):
    """Regression: with k=2, kept counts span BOTH token copies, so the
    drop-fraction denominator must be all-copies routed counts — 1 - kept/
    first-choice-only went to -1 in the no-drop regime."""
    from deepspeed_tpu.monitor.monitor import moe_gate_events

    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 16, 8)), jnp.float32)
    layer = MOELayer(expert=_TinyExpert(), model_dim=8, num_experts=4, k=2,
                     capacity_factor=cf, eval_capacity_factor=cf, min_capacity=1,
                     route="sorted")
    variables = layer.init(jax.random.PRNGKey(0), x)
    (_, _, _), ivars = layer.apply(variables, x, mutable=["intermediates"])
    inter = ivars["intermediates"]
    routed = np.asarray(inter["routed_counts"][0])
    kept = np.asarray(inter["kept_counts"][0])
    assert routed.sum() == 2 * 32  # both copies of every token
    assert kept.sum() <= routed.sum()
    events = moe_gate_events(
        {"moe": {"exp_counts": np.asarray(inter["exp_counts"][0]),
                 "kept_counts": kept, "routed_counts": routed,
                 "capacity_slots": int(inter["capacity_slots"][0])}}, step=0)
    df = dict((e[0], e[1]) for e in events)["MoE/moe/drop_fraction"]
    assert 0.0 <= df <= 1.0, df
    if cf == 8.0:
        assert df == 0.0  # generous capacity: nothing dropped
    else:
        assert df > 0.0  # tight capacity must drop second choices


# ---------------------------------------------------------------------------
# the [G,S,E,C] elimination guarantee — enforced through graft-lint R001
# (analysis/rules.py), the single source of truth; the hand-written jaxpr
# scanner this file used to carry lives there now, shared with the CI gate
# ---------------------------------------------------------------------------
def _r001_findings(route, k=1):
    """R001 findings for a fwd+bwd MOELayer step traced under ``route``."""
    from deepspeed_tpu.analysis import check_program
    from deepspeed_tpu.moe.sharded_moe import sec_signature

    S, M, E = 16, 8, 4
    cf = 1.0
    x = jnp.zeros((2, S // 2, M), jnp.float32)
    layer = MOELayer(expert=_TinyExpert(), model_dim=M, num_experts=E, k=k,
                     capacity_factor=cf, eval_capacity_factor=cf, min_capacity=1,
                     route=route)
    variables = layer.init(jax.random.PRNGKey(0), x)

    def loss(v, xx):
        (out, l_aux, _), _ = layer.apply(v, xx, mutable=["intermediates"])
        return (out**2).sum() + l_aux

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(variables, x)
    return check_program(jaxpr, rules=["R001"], name=f"moe_{route}_k{k}",
                         metadata={"moe_sec": [sec_signature(S, E, cf, 1, k=k)]})


@pytest.mark.parametrize("k", [1, 2])
def test_sorted_route_jaxpr_has_no_gsec_tensor(k):
    # the dense route must trip R001 (sanity: the analyzer can see the
    # signature tensor) and the sorted route's whole fwd+bwd program must
    # not
    assert _r001_findings("dense", k), "R001 failed to find [S,E,C] in the dense route"
    assert not _r001_findings("sorted", k), "sorted route still materializes [*,S,E,C]"


def test_sorted_train_step_jaxpr_has_no_gsec_tensor():
    """Model-level acceptance: the fwd+bwd jaxpr of a GPT-2-MoE loss with
    route=sorted contains no [*, S, E, C]-shaped intermediate anywhere
    (including sub-jaxprs under remat/scan) — per graft-lint R001."""
    from deepspeed_tpu.analysis import check_program
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.moe.sharded_moe import sec_signature

    cfg = get_gpt2_config("test", n_layer=2, moe_num_experts=4, moe_layer_freq=2,
                          moe_capacity_factor=2.0, moe_min_capacity=4,
                          moe_route="sorted")
    model = GPT2LMHeadModel(cfg)
    ids = jnp.zeros((4, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    S = 4 * 32  # one group (no topology)

    def loss(v):
        logits, aux = model.apply(v, ids)
        return logits.astype(jnp.float32).sum() + aux

    jaxpr = jax.make_jaxpr(jax.grad(loss))(variables)
    findings = check_program(
        jaxpr, rules=["R001"], name="gpt2_moe_sorted_train_step",
        metadata={"moe_sec": [sec_signature(S, 4, 2.0, 4, k=1)]})
    assert not findings, \
        f"sorted train step still materializes [*,S,E,C]: {[f.message for f in findings]}"


# ---------------------------------------------------------------------------
# sharded: EP>=2 end-to-end with route=sorted
# ---------------------------------------------------------------------------
def test_moe_gpt2_trains_sorted_on_expert_mesh():
    """GPT-2-MoE trains with route=sorted (via the engine's "moe" config
    block) on an expert=4 × fsdp=2 mesh: loss falls, expert params stay
    expert-axis sharded — the EP>=2 dryrun for the sorted route."""
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    topo = MeshTopology(expert=4, data=1, fsdp=2)
    cfg = get_gpt2_config("test", n_layer=2, moe_num_experts=4, moe_layer_freq=2,
                          moe_capacity_factor=2.0, moe_min_capacity=4)
    model = GPT2LMHeadModel(cfg)
    ds_config = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "moe": {"route": "sorted", "kernel": "xla"},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config, topology=topo)
    assert engine.module.config.moe_route_kernel == "xla"
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    moe_kernel = engine.state.params["h_1"]["moe"]["deepspeed_moe"]["experts"]["deepspeed_experts"]["c_fc"]["kernel"]
    spec = moe_kernel.sharding.spec
    assert "expert" in jax.tree.leaves(tuple(spec)), f"expert axis missing from {spec}"

    # expert-load observability rides the same engine (monitor satellite)
    stats = engine.moe_gate_stats(batch)
    assert stats, "no MoE gate stats collected"
    for s in stats.values():
        assert s["exp_counts"].sum() == 8 * 32
        assert np.all(s["kept_counts"] <= s["exp_counts"])
        assert s["capacity_slots"] > 0

    from deepspeed_tpu.monitor.monitor import moe_gate_events
    events = moe_gate_events(stats, step=1)
    names = {e[0] for e in events}
    assert any(n.endswith("drop_fraction") for n in names)
    assert any(n.endswith("capacity_utilization") for n in names)
