"""The held route (``MOELayer.experts_held``) and the grouped matmul under
``jax.grad``: what a training step differentiates for the first time. The
held route's gradients against the uncut sorted route restricted to the held
experts (the other experts' down projections zeroed, so they add nothing and
take no gradient), over every size of row buffer ``_row_rungs`` offers and
with a held expert that no token chooses; the grouped matmul's backward, both
implementations, with group sizes that add up to less than the rows."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama import LlamaConfig, LlamaMLP
from deepspeed_tpu.moe.sharded_moe import HELD_COUNTS, MOELayer, STEP_COUNTS, _row_rungs
from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

EXPERTS, TOP_K, TOKENS, WIDTH = 32, 2, 8192, 16
STARVED = 1     # a held expert the router never picks
CFG = LlamaConfig(hidden_size=WIDTH, intermediate_size=WIDTH, moe_activation="relu")


def layer(held=None, k=TOP_K):
    count = EXPERTS if held is None else held[1]
    return MOELayer(expert=LlamaMLP(CFG, num_experts=count), model_dim=WIDTH,
                    num_experts=EXPERTS, k=k, drop_tokens=False, norm_topk_prob=True,
                    route="sorted", route_kernel="xla", experts_held=held)


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.standard_normal((1, TOKENS, WIDTH)), jnp.float32)
    # what the gate reads: its first feature is one everywhere, and the
    # starved expert's weight on it is far below any logit
    read = jnp.asarray(rng.standard_normal((1, TOKENS, WIDTH)), jnp.float32).at[..., 0].set(1.0)
    params = nn.meta.unbox(layer().init(jax.random.PRNGKey(0), tokens, router_input=read)["params"])
    params = jax.tree.map(lambda p: p * 10.0, params)
    params["gate"]["wg"] = params["gate"]["wg"].at[0, STARVED].set(-1e4)
    cotangent = jnp.asarray(rng.standard_normal(tokens.shape), jnp.float32)
    return tokens, read, params, cotangent


def test_the_rungs_the_cases_below_reach():
    assert _row_rungs(TOKENS * TOP_K) == (1024, 4096, 16384)
    # a training step: one rung at twice the held experts' share of all
    assert _row_rungs(TOKENS * 3, 6 / EXPERTS) == (9216, 24576)
    assert _row_rungs(TOKENS * 3, 3 / EXPERTS) == (4608, 24576)
    assert _row_rungs(TOKENS * TOP_K, 16 / EXPERTS) == (16384,)
    assert _row_rungs(98304, 16 / 64) == (49152, 98304)     # the SmallThinker cell's layer


@pytest.mark.parametrize("held, buffered, training",
                         [((0, 2), 1024, False), ((0, 6), 4096, False), ((0, 16), 16384, False),
                          ((0, 6), 9216, True), ((0, 3), 24576, True)],
                         ids=["sixteenth", "quarter", "every-copy", "training-twice-the-share",
                              "training-overrun"])
def test_held_route_gradients_match_the_uncut_route_restricted(operands, held, buffered,
                                                               training):
    tokens, read, params, cotangent = operands
    bank = params["experts"]["deepspeed_experts"]
    first, count = held
    # a training step (``deterministic`` False) takes its own ladder; top 3
    # there, since a top-2 gate samples its second expert while it trains
    k = 3 if training else TOP_K
    how = dict(deterministic=not training, rngs={"gating": jax.random.PRNGKey(7)})
    if training and held == (0, 3):
        # every token's first choice is a held expert: more rows than twice
        # the share, so the step falls through to every copy
        params = jax.tree.map(lambda p: p, params)
        params["gate"]["wg"] = params["gate"]["wg"].at[0, 0].set(50.0)

    def whole_loss(gate, mine, tokens, read):
        full = jax.tree.map(lambda p: p, bank)
        # the held experts' weights where they were; the others add nothing
        full = {name: {"kernel": leaf["kernel"].at[first:first + count].set(mine[name]["kernel"])}
                for name, leaf in full.items()}
        keep = (jnp.arange(EXPERTS) >= first) & (jnp.arange(EXPERTS) < first + count)
        full["down_proj"]["kernel"] = full["down_proj"]["kernel"] * keep[:, None, None]
        out, _, _ = layer(k=k).apply({"params": {"gate": gate, "experts": {"deepspeed_experts": full}}},
                                  tokens, router_input=read, **how)
        return jnp.sum(out * cotangent)

    def held_loss(gate, mine, tokens, read):
        (out, _, _), counted = layer(held, k).apply(
            {"params": {"gate": gate, "experts": {"deepspeed_experts": mine}}},
            tokens, router_input=read, mutable=[STEP_COUNTS], **how)
        return jnp.sum(out * cotangent), counted[STEP_COUNTS]["moe_rows"]

    mine = jax.tree.map(lambda p: p[first:first + count], bank)
    args = (params["gate"], mine, tokens, read)
    # both jitted: eagerly each backward is dispatched an operation at a time
    want = jax.jit(jax.grad(whole_loss, argnums=(0, 1, 2, 3)))(*args)
    got, counts = jax.jit(jax.grad(held_loss, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    counts = dict(zip(HELD_COUNTS, np.asarray(counts)))
    assert counts["rows_buffered"] == buffered          # the rung this case is for
    assert counts["copies"] == TOKENS * k
    assert counts["experts_touched"] == count - 1       # all but the starved one
    assert counts["rows_routed"] <= counts["rows_visited"] <= buffered
    assert counts["load_max"] >= counts["rows_routed"] / count

    for name, g, w in zip(("gate", "bank", "tokens", "router input"), got, want):
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g), jax.tree.leaves(w)):
            scale = float(jnp.max(jnp.abs(b)))
            assert scale > 0 and bool(jnp.isfinite(a).all()), (name, path)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * scale,
                                       err_msg=f"{name} {jax.tree_util.keystr(path)}")
    # the expert no token chose took no gradient, on either side
    for leaf in jax.tree.leaves(got[1]):
        assert float(jnp.abs(leaf[STARVED - first]).max()) == 0.0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_grouped_matmul_backward_with_sizes_short_of_the_rows(impl):
    """512 rows, of which the groups hold 300 (one group empty): d lhs over
    the grouped rows and d rhs against a group-at-a-time dense product; rows
    past the groups are masked out of the loss, as the held route masks them."""
    rng = np.random.default_rng(1)
    rows, k, n = 512, 128, 128
    sizes = jnp.asarray([100, 0, 130, 70], jnp.int32)
    total = int(sizes.sum())
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, k, n)), jnp.float32)
    cotangent = jnp.asarray(rng.standard_normal((rows, n)), jnp.float32)
    real = (jnp.arange(rows) < total)[:, None]
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(rows), side="right").clip(max=3)

    def kernel_loss(lhs, rhs):
        out = grouped_matmul(lhs, rhs, sizes, impl=impl, interpret=True if impl == "pallas" else None)
        return jnp.sum(jnp.where(real, out, 0.0) * cotangent)

    def dense_loss(lhs, rhs):
        with jax.default_matmul_precision("highest"):
            out = jnp.einsum("rk,rkn->rn", lhs, rhs[group])
        return jnp.sum(jnp.where(real, out, 0.0) * cotangent)

    got = jax.grad(kernel_loss, argnums=(0, 1))(lhs, rhs)
    want = jax.grad(dense_loss, argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(got[0][:total]), np.asarray(want[0][:total]),
                               atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), atol=1e-3, rtol=1e-4)
    assert float(jnp.abs(got[1][1]).max()) == 0.0       # the empty group's weights
