"""DeepSeek-V3.2's family (``models/deepseek_v3.py`` with ``rope_scaling``,
``n_group`` > 1 and an indexer in every layer) at the tiny preset on the CPU:
the package against the plain reference (``benchmarks/reference/deepseek_v32.py``)
on seeded weights, logits AND the sets every layer's indexer chose, through the
full forward pass and through ragged chunks and decode over the serving cache,
at a size where ``index_topk`` binds in every layer and positions pass YaRN's
original context; the group-limited route against the reference on drawn
scores and on hand-made ties; ``n_group`` 1 and no scaling as they were; YaRN's
frequencies and scale against the closed form; the two rotations of one layer;
the chip's share against the uncut layer; and the scheduler with its counters."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import deepseek_v32 as family
from benchmarks.reference import deepseek_v32 as ref
from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler, Request, ServingConfig
from deepspeed_tpu.inference.serving.programs import (make_slot_cache, slot_capacity)
from deepspeed_tpu.models import deepseek_v3 as package
from deepspeed_tpu.models.common import INDEX_KEY_LEAVES, LATENT_LEAVES
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Block, DeepseekV3ForCausalLM,
                                              get_deepseek_v3_config)
from deepspeed_tpu.moe.sharded_moe import _topk_decisions, group_limited, topkrouting
from deepspeed_tpu.utils import trace

EXPERTS, GROUPS, KEPT, QUARTER = 16, 4, 2, 4
POSITIONS, TOP_K, CHUNK, LAYERS = 128, 24, 16, 4


def build(held=None, **overrides):
    cfg = get_deepseek_v3_config("deepseek-v3.2-test", experts_held=held,
                                 decode_cache_len=POSITIONS, **overrides)
    return DeepseekV3ForCausalLM(cfg)


def sizes_of(cfg, first=0):
    y = cfg.rope_scaling
    yarn = y and ref.Yarn(y.factor, y.original_max_position_embeddings, y.beta_fast, y.beta_slow,
                          y.mscale, y.mscale_all_dim)
    return ref.Sizes(n_layer=cfg.num_hidden_layers, n_dense=cfg.first_k_dense_replace,
                     d_nope=cfg.qk_nope_head_dim, d_rope=cfg.qk_rope_head_dim,
                     rank=cfg.kv_lora_rank, theta=cfg.rope_theta, index_top_k=cfg.index_topk,
                     top_k=cfg.num_experts_per_tok, n_group=cfg.n_group,
                     topk_group=cfg.topk_group, routed_scale=cfg.routed_scaling_factor, yarn=yarn,
                     experts_first=first, eps=cfg.rms_norm_eps, index_eps=cfg.rms_norm_eps)


def reference(params, ids, sizes, with_allowed=False):
    """``ref.forward`` as one program: eagerly it is dispatched an operation
    at a time, several hundred of them for every new length."""
    return jax.jit(lambda flat: ref.forward(flat, ids, sizes, with_allowed=with_allowed))(
        family.to_reference(params))


@pytest.fixture(scope="module")
def whole():
    """The uncut model and its seeded weights (float32), every matrix three
    times the plain draw: the softmaxes are then peaked, so that WHICH
    positions a query reads moves its logits; the selection bias ten times,
    so that it moves which experts and which groups are chosen."""
    module = build()
    params = nn.meta.unbox(jax.jit(module.init)(jax.random.PRNGKey(58),
                                                jnp.zeros((1, 8), jnp.int32))["params"])

    def scale(path, p):
        if getattr(path[-1], "key", "") == "e_score_correction_bias":
            return p * 10.0
        return p * 3.0 if p.ndim >= 2 else p

    return module, jax.tree_util.tree_map_with_path(scale, params)


def held_params(params, first, count):
    """The same weights with only experts ``[first, first + count)`` in each bank."""
    def cut(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        return leaf[first:first + count] if "deepspeed_experts" in names else leaf
    return jax.tree_util.tree_map_with_path(cut, params)


def ids_of(n, length, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n, length)).astype(np.int32)


def leaves_named(cache, names):
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", None) in names]


def chosen_sets(intermediates):
    """``[layer] -> [b, l, positions] bool`` of what the indexed layers chose."""
    return {name: np.asarray(layer["self_attn"]["dsa_chosen"][0])
            for name, layer in intermediates.items() if "dsa_chosen" in layer.get("self_attn", {})}


# ---------------------------------------------------------------------------
# the package against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("held", [None, (0, 4), (4, 2)], ids=str)
def test_full_forward_matches_the_reference_logits_and_chosen_sets(whole, held):
    module, params = whole
    if held:
        module, params = build(held), held_params(params, *held)
    ids = ids_of(2, 100)
    want, masks = reference(params, ids, sizes_of(module.config, held[0] if held else 0),
                            with_allowed=True)
    got, state = jax.jit(lambda p: module.apply({"params": p}, jnp.asarray(ids),
                                                mutable=["intermediates"]))(params)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)
    chosen = chosen_sets(state["intermediates"])
    assert sorted(chosen) == [f"layers_{i}" for i in range(LAYERS)]      # every layer indexed
    for i in range(LAYERS):
        np.testing.assert_array_equal(chosen[f"layers_{i}"][:, :, :100], np.asarray(masks[i]))
        # the selection binds: the last query chose TOP_K of its 100 positions
        assert chosen[f"layers_{i}"][:, -1].sum(axis=-1).tolist() == [TOP_K, TOP_K]


@pytest.mark.parametrize("change", ["plain_rope", "flat_route", "interleaved_indexer"])
def test_each_mechanism_moves_the_logits_at_this_size(whole, change):
    """What the three new mechanisms are worth here: the model without YaRN,
    with a flat top-k, or with the indexer turned in the layer's pairing is NOT
    the reference's, by far more than the comparison's tolerance."""
    module, params = whole
    ids = ids_of(2, 100)
    want = np.asarray(reference(params, ids, sizes_of(module.config)))
    other = build(**{"plain_rope": dict(rope_scaling=None),
                     "flat_route": dict(n_group=1, topk_group=1),
                     "interleaved_indexer": dict(index_rope_interleave=True)}[change])
    got = jax.jit(lambda p: other.apply({"params": p}, jnp.asarray(ids)))(params)
    assert np.abs(np.asarray(got) - want).max() > 1e-2


# ---------------------------------------------------------------------------
# the route
# ---------------------------------------------------------------------------
def route_sizes(k=4, n_group=GROUPS, topk_group=KEPT):
    return ref.Sizes(n_layer=1, n_dense=0, d_nope=16, d_rope=8, rank=32, theta=1e4, index_top_k=0,
                     top_k=k, n_group=n_group, topk_group=topk_group, routed_scale=2.5)


def package_route(logits, bias, k=4, groups=(GROUPS, KEPT), used=None):
    _, routing, counts = topkrouting(jnp.asarray(logits), k, 1.0, 4, drop_tokens=False,
                                     normalize=True, used_token=used, score="sigmoid",
                                     select_bias=jnp.asarray(bias), scale=2.5, positions=False,
                                     groups=groups)
    return routing, counts


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_route_is_the_references_on_drawn_scores(seed):
    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 1.5, (64, EXPERTS)).astype(np.float32)
    bias = rng.normal(0, 0.3, (EXPERTS,)).astype(np.float32)
    routing, _ = package_route(logits, bias)
    s = jax.nn.sigmoid(jnp.asarray(logits))
    chosen, kept = ref.route(s, bias, route_sizes())
    np.testing.assert_array_equal(routing.expert, chosen)
    np.testing.assert_array_equal(routing.group_kept, kept)
    assert np.asarray(kept).sum(axis=-1).tolist() == [KEPT] * 64
    # every chosen expert lies in a kept group; the weights are the UNBIASED
    # scores of the chosen, over their sum, times the scale
    assert np.take_along_axis(np.asarray(kept), np.asarray(chosen) // (EXPERTS // GROUPS), 1).all()
    picked = np.take_along_axis(np.asarray(s), np.asarray(chosen), 1)
    np.testing.assert_allclose(routing.weight, picked / picked.sum(-1, keepdims=True) * 2.5,
                               rtol=1e-6)
    # and against the reference's dense weights over all the experts
    dense = np.zeros((64, EXPERTS), np.float32)
    np.put_along_axis(dense, np.asarray(routing.expert), np.asarray(routing.weight), 1)
    want = ref.router({"router": np.eye(EXPERTS, dtype=np.float32), "router_bias": bias},
                      jnp.asarray(logits), route_sizes())
    np.testing.assert_allclose(dense, want, rtol=1e-6)


def _scores(values):
    """Logits whose sigmoid is ``values`` (exactly representable scores)."""
    v = np.asarray(values, np.float64)
    return np.log(v / (1 - v)).astype(np.float32)


def test_the_route_on_hand_made_ties():
    zero = np.zeros(8, np.float32)
    sizes = route_sizes(k=2, n_group=4, topk_group=2)
    # four groups of two. Groups tied: every group's two largest sum to 1.0;
    # the two LOWER groups are kept, then the two largest of their four
    logits = np.zeros((1, 8), np.float32)                     # every score 0.5
    routing, _ = package_route(logits, zero, k=2, groups=(4, 2))
    assert routing.group_kept.tolist() == [[True, True, False, False]]
    assert routing.expert.tolist() == [[0, 1]]                # experts tied: the lower index
    chosen, kept = ref.route(jax.nn.sigmoid(jnp.asarray(logits)), zero, sizes)
    assert chosen.tolist() == [[0, 1]] and kept.tolist() == [[True, True, False, False]]
    # a group is scored by its two largest SUMMED: group 3 holds the single
    # largest score (0.9 + 0.1 = 1.0) and loses to groups 1 and 2 (0.6 + 0.5)
    logits = _scores([[0.2, 0.2, 0.6, 0.5, 0.5, 0.6, 0.9, 0.1]])
    routing, _ = package_route(logits, zero, k=2, groups=(4, 2))
    assert routing.group_kept.tolist() == [[False, True, True, False]]
    assert routing.expert.tolist() == [[2, 5]]                # 0.6 twice: the lower index first
    chosen, kept = ref.route(jax.nn.sigmoid(jnp.asarray(logits)), zero, sizes)
    assert chosen.tolist() == [[2, 5]] and kept.tolist() == [[False, True, True, False]]
    # a biased choice with unbiased weights: the bias lifts group 0 over group
    # 2 and expert 1 over expert 0; the weights are the scores 0.2 and 0.6
    bias = np.asarray([0.0, 0.8, 0, 0, 0, 0, 0, 0], np.float32)
    routing, _ = package_route(logits, bias, k=2, groups=(4, 2))
    assert routing.group_kept.tolist() == [[True, True, False, False]]
    assert routing.expert.tolist() == [[1, 2]]
    np.testing.assert_allclose(routing.weight, [[0.2 / 0.8 * 2.5, 0.6 / 0.8 * 2.5]], rtol=1e-6)
    chosen, _ = ref.route(jax.nn.sigmoid(jnp.asarray(logits)), bias, sizes)
    assert chosen.tolist() == [[1, 2]]


def test_a_flat_top_8_that_crosses_five_groups_is_held_to_four():
    """256 experts in 8 groups of 32, four kept, eight a token (the published
    sizes): a token whose eight largest scores lie in five groups takes only
    those of the four best groups, and the next best inside them."""
    scores = np.full((1, 256), 0.1)
    # the flat top-8: two each in groups 0-2, one each in groups 3 and 4
    for at, v in ((0, 0.9), (1, 0.8), (32, 0.9), (33, 0.8), (64, 0.9), (65, 0.8), (96, 0.7),
                  (128, 0.75), (97, 0.3), (2, 0.2)):
        scores[0, at] = v
    logits, zero = _scores(scores), np.zeros(256, np.float32)
    flat, _ = package_route(logits, zero, k=8, groups=None)
    assert sorted(flat.expert[0].tolist()) == [0, 1, 32, 33, 64, 65, 96, 128]
    assert len({e // 32 for e in flat.expert[0].tolist()}) == 5
    routing, _ = package_route(logits, zero, k=8, groups=(8, 4))
    # group 3 scores 0.7 + 0.3 = 1.0, group 4 0.75 + 0.1 = 0.85: group 3 is the fourth
    assert routing.group_kept[0].tolist() == [True, True, True, True] + [False] * 4
    assert sorted(routing.expert[0].tolist()) == [0, 1, 32, 33, 64, 65, 96, 97]
    chosen, kept = ref.route(jax.nn.sigmoid(jnp.asarray(logits)), zero,
                             route_sizes(k=8, n_group=8, topk_group=4))
    np.testing.assert_array_equal(routing.expert, chosen)
    np.testing.assert_array_equal(routing.group_kept, kept)


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("positions", [False, True])
def test_one_group_is_the_flat_route_bit_for_bit(bias, positions):
    """``n_group`` 1 (no ``groups``) gives what the core gave before it knew of
    groups: the top-k of score + bias by ``jax.lax.top_k``, the unbiased scores
    as weights; and one group of which one is kept is that too."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(0, 1.5, (48, EXPERTS)).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.3, (EXPERTS,)).astype(np.float32)) if bias else None
    kw = dict(used_token=None, score="sigmoid", select_bias=b, scale=2.5, positions=positions)
    _, plain, counts, _ = _topk_decisions(logits, 4, 1.0, 4, False, True, **kw)
    assert not hasattr(plain, "group_kept")
    gates = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(gates if b is None else gates + b[None, :], 4)
    weights = jnp.take_along_axis(gates, experts, axis=1)
    weights = weights / jnp.maximum(weights.sum(axis=1, keepdims=True),
                                    jnp.finfo(jnp.float32).eps) * 2.5
    np.testing.assert_array_equal(plain.expert, experts)
    np.testing.assert_array_equal(plain.weight, weights)
    _, one, counts_one, _ = _topk_decisions(logits, 4, 1.0, 4, False, True, groups=(1, 1), **kw)
    for got, want in zip(one[:4], plain[:4]):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(counts_one, counts)
    assert np.asarray(one.group_kept).all()


def test_a_group_count_that_does_not_divide_the_experts_is_refused():
    with pytest.raises(ValueError, match="group-limited"):
        group_limited(jnp.zeros((2, 10)), 4, 2)


# ---------------------------------------------------------------------------
# YaRN; the two rotations
# ---------------------------------------------------------------------------
PUBLISHED = ref.Yarn(40.0, 4096, 32.0, 1.0, 1.0, 1.0)


def test_yarns_frequencies_and_scale_are_the_closed_form_at_the_published_sizes():
    kind = get_deepseek_v3_config("deepseek-v3.2").kind_of(0)
    inv_freq, factor = kind.frequencies()
    want, want_factor = ref.yarn_frequencies(64, 1e4, PUBLISHED)
    np.testing.assert_array_equal(inv_freq, want.astype(np.float32))
    assert factor == want_factor == 1.0
    # by hand: d(32) = 10.47, d(1) = 22.51: pairs 0-10 plain, 23-31 over 40, a line between
    plain = 1e4 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(want[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(want[23:], plain[23:] / 40, rtol=1e-12)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(want[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 40 * ramp,
                               rtol=1e-12)
    m = 0.1 * np.log(40.0) + 1.0
    assert kind.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert kind.softmax_scale == pytest.approx(192 ** -0.5 * 1.8739, rel=1e-4)
    assert kind.softmax_scale == pytest.approx(
        ref.softmax_scale(ref.Sizes(1, 0, 128, 64, 512, 1e4, 0, 8, 8, 4, 2.5, yarn=PUBLISHED)),
        rel=1e-12)
    # mscale over mscale_all_dim on cosine and sine, where the two differ
    other = package.YarnScaling(factor=40.0, mscale=0.707, mscale_all_dim=1.0)
    kind = get_deepseek_v3_config("deepseek-v3.2", rope_scaling=other).kind_of(0)
    assert kind.frequencies()[1] == pytest.approx((0.0707 * np.log(40) + 1) / m, rel=1e-12)


def test_no_scaling_is_theta_alone_bit_for_bit():
    kind = get_deepseek_v3_config("joyai-llm-flash").kind_of(0)
    inv_freq, factor = kind.frequencies()
    np.testing.assert_array_equal(inv_freq, jnp.asarray(32e6 ** (-np.arange(0, 64, 2) / 64),
                                                        jnp.float32))
    assert factor == 1.0 and kind.softmax_scale == (128 + 64) ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 64))
    at = jnp.broadcast_to(jnp.arange(9) + 5000, (2, 9))
    np.testing.assert_array_equal(package.rope_turn(x, at, inv_freq, factor),
                                  package.rotate_interleaved(x, at, 32e6))
    # the published dict builds the dataclass; another type is refused by name
    cfg = get_deepseek_v3_config("deepseek-v3-test", rope_scaling={
        "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
    assert cfg.rope_scaling == package.YarnScaling(40, 4096, 32, 1, 1, 1)
    with pytest.raises(NotImplementedError, match="linear"):
        get_deepseek_v3_config("deepseek-v3-test", rope_scaling={"type": "linear", "factor": 2})


@pytest.mark.parametrize("interleaved", [True, False])
def test_both_rotations_are_the_references_past_the_original_context(interleaved):
    """Positions 4,000-4,200 and 30,000 of the published rotation, in the
    layer's pairing (neighbours) and in the indexer's (half-split)."""
    kind = get_deepseek_v3_config("deepseek-v3.2").kind_of(0)
    inv_freq, factor = kind.frequencies()
    at = np.concatenate([np.arange(4000, 4200), [30000, 32767]])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, len(at), 3, 64))
    got = package.rope_turn(x, jnp.asarray(at)[None], inv_freq, factor, interleaved)
    sizes = ref.Sizes(1, 0, 128, 64, 512, 1e4, 0, 8, 8, 4, 2.5, yarn=PUBLISHED)
    want = ref.rope(jnp.moveaxis(x, 1, 2), jnp.asarray(at), sizes, interleaved)
    np.testing.assert_allclose(jnp.moveaxis(got, 1, 2), want, atol=2e-6)
    # the two pairings are not each other's
    assert np.abs(np.asarray(got) - np.asarray(
        package.rope_turn(x, jnp.asarray(at)[None], inv_freq, factor, not interleaved))).max() > 0.5


# ---------------------------------------------------------------------------
# the serving cache: ragged chunks, then decode, logits and sets
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def observed(whole):
    module, params = build((0, 4)), held_params(whole[1], 0, 4)

    def one(params, cache, write_pos, fed, ids):
        """One call of the model as a tick makes it, with the logits and the
        indexed layers' chosen sets out beside the cache."""
        from deepspeed_tpu.inference.serving import programs as p
        model_cache, held = p.without_next_tokens(cache)
        logits, state = module.apply(
            {"params": params, "cache": p.with_write_positions(model_cache, write_pos, fed)},
            ids, decode=True, mutable=["cache", "intermediates"])
        return p.with_next_tokens(state["cache"], held), logits, state["intermediates"]

    return module, params, jax.jit(one)


def _int(*values):
    return jnp.asarray(values, jnp.int32)


def test_ragged_chunks_then_decode_match_the_reference_logits_and_sets(observed):
    """Slots 1 and 2 of four take prompts of 75 and 52 tokens in chunks of 16
    (ragged last chunks) and decode 12 more, fed the sequence's own tokens;
    every real position's logits and all four layers' chosen sets are the
    reference's full forward pass's. Every layer has an index-key pool beside
    its latent pool; the route's group counter is left for the host."""
    module, params, step = observed
    ids = ids_of(2, 90, seed=3)
    want, masks = reference(params, ids, sizes_of(module.config, 0), with_allowed=True)
    want, masks = np.asarray(want), [np.asarray(m) for m in masks]
    cache = make_slot_cache(module, 4)
    parked = slot_capacity(cache)
    assert parked == POSITIONS
    assert [leaf.shape for leaf in leaves_named(cache, LATENT_LEAVES)] == \
        [(4, 1, 32 + 8, POSITIONS)] * LAYERS
    assert [leaf.shape for leaf in leaves_named(cache, INDEX_KEY_LEAVES)] == \
        [(4, 1, 16, POSITIONS)] * LAYERS
    lens, done = (75, 52), [0, 0]

    def check(logits, sets, slot, k, first, n):
        np.testing.assert_allclose(np.asarray(logits)[slot, :n], want[k, first:first + n],
                                   atol=5e-5, rtol=0)
        for i in range(LAYERS):
            got = sets[f"layers_{i}"][slot, :n, :90]
            np.testing.assert_array_equal(got[:, :first + n], masks[i][k, first:first + n, :first + n])
            assert not got[:, first + n:].any()

    while any(d < n for d, n in zip(done, lens)):
        write_pos, fed, batch = np.full(4, parked), np.zeros(4), np.zeros((4, CHUNK), np.int32)
        for k, slot in enumerate((1, 2)):
            n = min(CHUNK, lens[k] - done[k])
            if n > 0:
                write_pos[slot], fed[slot] = done[k], n
                batch[slot, :n] = ids[k, done[k]:done[k] + n]
        cache, logits, state = step(params, cache, _int(*write_pos), _int(*fed), jnp.asarray(batch))
        # three expert layers: real rows routed, of which some reach group 0
        groups = np.asarray(leaves_named(cache, ("moe_group_rows",)))
        assert groups.shape == (LAYERS - 1, 2) and (groups[:, 1] == fed.sum()).all()
        assert (groups[:, 0] <= groups[:, 1]).all()
        sets = chosen_sets(state)
        for k, slot in enumerate((1, 2)):
            if fed[slot]:
                check(logits, sets, slot, k, done[k], int(fed[slot]))
                done[k] += int(fed[slot])
    for at in range(12):
        write_pos = np.full(4, parked)
        tokens = np.zeros((4, 1), np.int32)
        for k, slot in enumerate((1, 2)):
            write_pos[slot], tokens[slot, 0] = lens[k] + at, ids[k, lens[k] + at]
        cache, logits, state = step(params, cache, _int(*write_pos), _int(1, 1, 1, 1),
                                    jnp.asarray(tokens))
        sets = chosen_sets(state)
        for k, slot in enumerate((1, 2)):
            check(logits, sets, slot, k, lens[k] + at, 1)


# ---------------------------------------------------------------------------
# the chip's share; the scheduler
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("count", [QUARTER, 2])
def test_the_shares_routed_parts_add_up_to_the_uncut_reference_layer(whole, count):
    """The guide's test of a share: the shares' expert layers (four quarters, a
    group each; eight of two, half a group each, as the cell's eight are a
    quarter of one), the shared expert and the residual counted once, add up
    to what the UNCUT REFERENCE gives for the whole layer."""
    module, params = whole
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, module.config.hidden_size))

    def layer(held):
        block = DeepseekV3Block(build(held).config, True, 1)
        p = params["layers_1"] if held is None else held_params(params, *held)["layers_1"]
        return block.apply({"params": p}, x)

    bp = ref.block_params(family.to_reference(params), 1)
    sizes = sizes_of(module.config)
    uncut = ref.feed_forward(bp, ref.attention(bp, x, sizes), sizes)
    np.testing.assert_allclose(layer(None), uncut, atol=3e-5)
    parts = [layer((first, count)) for first in range(0, EXPERTS, count)]
    # each part carries the attention's output, the residual and the shared expert
    alone = layer_without_routed(whole, x)
    np.testing.assert_allclose(sum(parts) - (len(parts) - 1) * alone, uncut, atol=5e-5)
    # a share whose group is not kept adds nothing for that token: some rows
    # of a part ARE the layer without its routed experts
    assert any((np.abs(np.asarray(part - alone)).max(axis=-1) == 0).any() for part in parts)


def layer_without_routed(whole, x):
    """The layer with every routed expert's down projection zeroed: the
    attention, the residual and the shared expert alone."""
    module, params = whole

    def zero(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        return jnp.zeros_like(leaf) if names[-3:-1] == ["deepspeed_experts", "down_proj"] else leaf

    block = DeepseekV3Block(module.config, True, 1)
    return block.apply({"params": jax.tree_util.tree_map_with_path(zero, params["layers_1"])}, x)


@pytest.fixture(scope="module")
def engine(whole):
    from deepspeed_tpu.parallel.topology import MeshTopology
    module, params = build((0, 2)), held_params(whole[1], 0, 2)
    return deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                        max_out_tokens=POSITIONS,
                                        topology=MeshTopology(devices=jax.devices()[:1]))


def test_the_scheduler_serves_it_and_counts_what_the_layers_read(engine):
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=4, page_size=16, kv_quant=False, prefill_chunk=CHUNK, prefill_interleave=2,
        prefix_cache="off"))
    before = dict(trace.recorder().counters)
    prompts = [ids_of(1, n, seed=n)[0] for n in (90, 33, 75, 20, 60)]
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    # one reference pass over the five, padded on the right to the longest:
    # the reference is causal, so a row's logits up to its length are its own
    fed = [np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)]) for r in reqs]
    ids = np.stack([np.pad(row, (0, max(map(len, fed)) - len(row))) for row in fed])
    want = np.asarray(reference(engine.params, ids, sizes_of(engine.module.config, 0)))
    for r, row, logits in zip(reqs, fed, want):
        assert list(r.output) == logits[len(r.prompt) - 1:len(row)].argmax(-1).tolist()
    counted = {k: v - before.get(k, 0) for k, v in trace.recorder().counters.items()}
    tokens = sum(len(p) for p in prompts)
    # four indexed layers: a query at t attends min(t + 1, TOP_K) of t + 1
    assert counted["dsa_positions_live_prefill"] == LAYERS * sum(
        n * (n + 1) // 2 for n in map(len, prompts))
    assert counted["dsa_positions_selected_prefill"] == LAYERS * sum(
        sum(min(t + 1, TOP_K) for t in range(len(p))) for p in prompts)
    assert 0 < counted["dsa_positions_selected_decode"] < counted["dsa_positions_live_decode"]
    assert counted["latent_positions_live_prefill"] > 0 and counted["moe_rows_routed_prefill"] > 0
    # the route's groups, three expert layers: every real row is counted, and
    # with two groups of four kept some of them reach group 0 and some do not
    # (half under an even router; this draw's bias favours group 0)
    assert counted["moe_rows_group_routed_prefill"] == (LAYERS - 1) * tokens
    assert counted["moe_rows_group_routed_decode"] == (LAYERS - 1) * 5 * 5
    kept = counted["moe_rows_group_kept_prefill"] / counted["moe_rows_group_routed_prefill"]
    assert 0.2 < kept < 0.9
    # no row reaches a held expert but through its kept group
    assert counted["moe_rows_routed_prefill"] <= 2 * counted["moe_rows_group_kept_prefill"]
    wide = engine.module.config
    assert counted["dsa_latent_bytes_written"] >= LAYERS * tokens * wide.latent_width * 4
    assert counted["dsa_index_key_bytes_written"] >= LAYERS * tokens * wide.index_head_dim * 4
