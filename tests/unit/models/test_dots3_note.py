"""dots3-note-prev's family (``models/deepseek_v3.py`` with ``layer_types``)
at the tiny preset on the CPU: the package against the plain reference
(``benchmarks/reference/dots3_note.py``) on seeded weights, logits AND the
sets the indexer chose, through the full forward pass and through ragged
chunks and decode over the serving cache, at a size where ``index_topk`` and
the window both bind and the window layers' ring wraps; what a tick may and
may not touch of the three kinds of pool; a ring's next tenant; the chip's
share against the uncut layer; the kernels against XLA's forms; and what the
scheduler refuses over a ring."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import dots3_note as family
from benchmarks.reference import dots3_note as ref
from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler, Request, ServingConfig
from deepspeed_tpu.inference.serving.programs import (build_decode_step, build_prefill_step,
                                                      make_apply_fn, make_slot_cache,
                                                      quantize_slot_cache, slot_capacity)
from deepspeed_tpu.models import deepseek_v3 as package
from deepspeed_tpu.models.common import (INDEX_KEY_LEAVES, LATENT_LEAVES, RING_LEAVES,
                                         SPARSE_READS, ring_pool_append)
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Block, DeepseekV3ForCausalLM,
                                              get_deepseek_v3_config)
from deepspeed_tpu.ops.pallas import latent_decode, sparse_index
from deepspeed_tpu.utils import trace

EXPERTS, QUARTER = 16, 4
POSITIONS, RING, WINDOW, TOP_K, CHUNK = 128, 32, 17, 24, 16


def build(held=None, **overrides):
    cfg = get_deepseek_v3_config("dots3-note-test", experts_held=held, decode_cache_len=POSITIONS,
                                 **overrides)
    return DeepseekV3ForCausalLM(cfg)


def sizes_of(cfg, first=0):
    kinds = []
    for i in range(cfg.num_hidden_layers):
        k = cfg.kind_of(i)
        kinds.append(ref.Kind(k.d_nope, k.d_rope, k.rank, k.q_rank, k.theta, window=k.window,
                              top_k=k.top_k))
    return ref.Sizes(kinds=tuple(kinds), n_dense=cfg.first_k_dense_replace,
                     hidden=cfg.hidden_size, top_k=cfg.num_experts_per_tok,
                     routed_scale=cfg.routed_scaling_factor, rescale=cfg.mla_lora_rescale,
                     experts_first=first, eps=cfg.rms_norm_eps)


def reference(params, ids, sizes, with_allowed=False):
    """``ref.forward`` as one program: eagerly it is dispatched an operation
    at a time, several hundred of them for every new length."""
    return jax.jit(lambda flat: ref.forward(flat, ids, sizes, with_allowed=with_allowed))(
        family.to_reference(params))


@pytest.fixture(scope="module")
def whole():
    """The uncut model and its seeded weights (float32), every matrix three
    times the plain draw: the softmaxes are then peaked, so that WHICH
    positions a query reads moves its logits."""
    module = build()
    params = nn.meta.unbox(jax.jit(module.init)(jax.random.PRNGKey(37),
                                                jnp.zeros((1, 8), jnp.int32))["params"])
    return module, jax.tree.map(lambda p: p * 3.0 if p.ndim >= 2 else p, params)


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
@pytest.mark.parametrize("held", [None, (4, 4)], ids=str)
def test_full_forward_matches_the_reference_logits_and_chosen_sets(whole, held):
    module, params = whole
    if held:
        module, params = build(held), held_params(params, *held)
    ids = ids_of(2, 100)
    want, masks = reference(params, ids, sizes_of(module.config, held[0] if held else 0),
                            with_allowed=True)
    got, state = jax.jit(lambda p: module.apply({"params": p}, jnp.asarray(ids),
                                                mutable=["intermediates"]))(params)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    chosen = chosen_sets(state["intermediates"])
    assert sorted(chosen) == ["layers_0", "layers_1"]
    for i in (0, 1):
        np.testing.assert_array_equal(chosen[f"layers_{i}"][:, :, :100], np.asarray(masks[i]))
        # the selection binds: the last query chose TOP_K of its 100 positions
        assert chosen[f"layers_{i}"][:, -1].sum(axis=-1).tolist() == [TOP_K, TOP_K]
    # and so does the window: the last query of a sliding layer reads WINDOW positions
    assert np.asarray(masks[2])[0, -1].sum() == WINDOW


def test_the_reference_reads_what_the_issue_says(whole):
    """The reference alone, on made-up index scores: the top-k by score at or
    before a query, all while fewer, equal scores to the lower position."""
    pos = jnp.arange(6)
    seen = pos[None, :] <= pos[:, None]
    scores = jnp.asarray([[0.0, 0, 0, 0, 0, 0], [1.0, 2, 0, 0, 0, 0], [3.0, 1, 2, 0, 0, 0],
                          [1.0, 1, 1, 1, 0, 0], [5.0, -0.0, 0.0, 7, 6, 0], [2, 2, 2, 2, 2, 2.0]])
    got = np.asarray(ref.top_positions(scores[None], 2, seen[None]))[0]
    assert got.astype(int).tolist() == [[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0], [1, 0, 1, 0, 0, 0],
                                        [1, 1, 0, 0, 0, 0], [0, 0, 0, 1, 1, 0], [1, 1, 0, 0, 0, 0]]


@pytest.mark.parametrize("k", [1, 3, 7, 64])
def test_the_bar_and_the_mask_choose_what_a_stable_sort_chooses(k):
    """``kth_largest`` + ``chosen_of`` against a stable sort, on scores full of
    ties, signed zeros and negatives, whole rows and runs of columns."""
    rng = np.random.default_rng(k)
    scores = rng.choice(np.asarray([-2.5, -0.0, 0.0, 1.0, 1.0, 3.25, 7.0], np.float32), (5, 48))
    valid = rng.random((5, 48)) < 0.8
    order = np.argsort(np.where(valid, -(scores + 0.0), np.inf), axis=-1, kind="stable")
    want = np.zeros_like(valid)
    for row in range(5):
        want[row, order[row, :k]] = True
    want &= valid
    keys, bar, quota = package.kth_largest(jnp.asarray(scores), jnp.asarray(valid), k)
    whole_rows, _ = package.chosen_of(keys, bar, quota)
    np.testing.assert_array_equal(np.asarray(whole_rows), want)
    tied, runs = None, []
    for at in range(0, 48, 16):
        run, tied = package.chosen_of(keys[:, at:at + 16], bar, quota, tied)
        runs.append(np.asarray(run))
    np.testing.assert_array_equal(np.concatenate(runs, axis=1), want)


# ---------------------------------------------------------------------------
# the serving cache: ragged chunks, then decode, logits and sets
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def programs(whole):
    module, params = build((4, 4)), held_params(whole[1], 4, 4)
    apply_fn = make_apply_fn(module)

    def stepper(build_step):
        step = build_step(apply_fn, False, 1.0, 0, 1.0)
        return jax.jit(step)

    def observed(params, cache, write_pos, fed, ids):
        """One call of the model as a tick makes it, with the logits and the
        indexed layers' chosen sets out beside the cache."""
        from deepspeed_tpu.inference.serving import programs as p
        model_cache, held = p.without_next_tokens(cache)
        logits, state = module.apply(
            {"params": params, "cache": p.with_write_positions(model_cache, write_pos, fed)},
            ids, decode=True, mutable=["cache", "intermediates"])
        return p.with_next_tokens(state["cache"], held), logits, state["intermediates"]

    return (module, params, stepper(build_prefill_step), stepper(build_decode_step),
            jax.jit(observed))


def _int(*values):
    return jnp.asarray(values, jnp.int32)


def test_ragged_chunks_then_decode_match_the_reference_logits_and_sets(programs):
    """Slots 1 and 2 of four take prompts of 75 and 52 tokens in chunks of 16
    (ragged last chunks, the ring of 32 wrapped twice) and decode 12 more, fed
    the sequence's own tokens; every real position's logits and both indexed
    layers' chosen sets are the reference's full forward pass's."""
    module, params, _, _, observed = programs
    ids = ids_of(2, 90, seed=3)
    want, masks = reference(params, ids, sizes_of(module.config, 4), with_allowed=True)
    want, masks = np.asarray(want), [np.asarray(m) for m in masks]
    cache = make_slot_cache(module, 4)
    parked = slot_capacity(cache)
    assert parked == POSITIONS
    lens, done = (75, 52), [0, 0]

    def check(logits, sets, slot, k, first, n):
        np.testing.assert_allclose(np.asarray(logits)[slot, :n], want[k, first:first + n],
                                   atol=3e-5, rtol=0)
        for i in (0, 1):
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
        cache, logits, state = observed(params, cache, _int(*write_pos), _int(*fed),
                                        jnp.asarray(batch))
        sets = chosen_sets(state)
        # the two fed slots' selections ran over their chunk's rows and (by
        # XLA) the whole extent; a window layer selects nothing
        at = SPARSE_READS.index("dsa_select_positions_read")
        assert sorted(int(leaf[at]) for leaf in leaves_named(cache, ("sparse_reads",))) == \
            [0, 0] + [int((fed > 0).sum()) * CHUNK * POSITIONS] * 2
        for k, slot in enumerate((1, 2)):
            if fed[slot]:
                check(logits, sets, slot, k, done[k], int(fed[slot]))
                done[k] += int(fed[slot])
    for step in range(12):
        write_pos = np.full(4, parked)
        tokens = np.zeros((4, 1), np.int32)
        for k, slot in enumerate((1, 2)):
            write_pos[slot], tokens[slot, 0] = lens[k] + step, ids[k, lens[k] + step]
        cache, logits, state = observed(params, cache, _int(*write_pos), _int(1, 1, 1, 1),
                                        jnp.asarray(tokens))
        sets = chosen_sets(state)
        for k, slot in enumerate((1, 2)):
            check(logits, sets, slot, k, lens[k] + step, 1)


def test_the_three_kinds_of_pool_are_sized_by_the_kind_of_layer(programs):
    module = programs[0]
    cache = make_slot_cache(module, 4)
    latents, keys, rings = (leaves_named(cache, names) for names in
                            (LATENT_LEAVES, INDEX_KEY_LEAVES, RING_LEAVES))
    assert [leaf.shape for leaf in latents] == [(4, 1, 32 + 8, POSITIONS)] * 2
    assert [leaf.shape for leaf in keys] == [(4, 1, 16, POSITIONS)] * 2
    assert [leaf.shape for leaf in rings] == [(4, 1, 48 + 8, RING)] * 2
    assert slot_capacity(cache) == POSITIONS                 # the pools', not the ring's
    assert package.window_ring_positions(513, 512) == 1024
    assert package.window_ring_positions(513, 256) == 768
    assert package.window_ring_positions(WINDOW, CHUNK, page=16) == RING


@pytest.mark.parametrize("leaf", ["cached_latent", "cached_index_key", "cached_window_latent"])
def test_an_int8_pool_of_any_kind_is_refused_by_name(programs, leaf):
    with pytest.raises(NotImplementedError, match=leaf):
        quantize_slot_cache({"layer": {leaf: jnp.zeros((4, 1, 8, 16))}})


def test_a_chunk_longer_than_the_ring_spares_is_refused(programs):
    module, params = programs[:2]
    cache = make_slot_cache(module, 4)
    step = build_prefill_step(make_apply_fn(module), False, 1.0, 0, 1.0)
    with pytest.raises(ValueError, match="window_ring_positions"):
        jax.eval_shape(step, params, cache, _int(0, 0, 0, 0), jnp.zeros((4, 17), jnp.int32),
                       _int(16, 16, 16, 16))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_parked_slots_write_nothing_and_other_slots_rows_are_bit_identical(programs, program):
    """A tick over slot 1 alone: every other slot's rows of every pool, rings
    among them, come back bit for bit (a parked slot's sentinel position,
    folded into a ring, would have landed on place 0)."""
    module, params, prefill, decode, _ = programs
    rng = np.random.default_rng(5)
    cache = jax.tree.map(
        lambda leaf: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype) if leaf.ndim == 4
        else leaf, make_slot_cache(module, 4))
    parked = slot_capacity(cache)
    before = [np.asarray(leaf) for leaf in
              leaves_named(cache, LATENT_LEAVES + INDEX_KEY_LEAVES + RING_LEAVES)]
    if program == "prefill":
        after, _ = prefill(params, cache, _int(parked, 40, parked, parked),
                           jnp.asarray(ids_of(4, CHUNK)), _int(0, 9, 0, 0))
        wrote = range(40, 40 + CHUNK)
    else:
        after, _ = decode(params, cache, _int(parked, 40, parked, parked))
        wrote = range(40, 41)
    after = [np.asarray(leaf) for leaf in
             leaves_named(after, LATENT_LEAVES + INDEX_KEY_LEAVES + RING_LEAVES)]
    for old, new in zip(before, after):
        np.testing.assert_array_equal(new[[0, 2, 3]], old[[0, 2, 3]])
        places = [p % old.shape[-1] for p in wrote]
        kept = [p for p in range(old.shape[-1]) if p not in places]
        np.testing.assert_array_equal(new[1][..., kept], old[1][..., kept])
        assert not np.array_equal(new[1][..., places], old[1][..., places])


def test_a_slot_that_joins_a_ring_reads_nothing_of_its_last_tenant(programs):
    """Slot 1 served 75 tokens (its ring wrapped); a new request joins it at
    position 0 with nothing zeroed: its tokens are those of the same request
    served on a fresh cache, and both are the reference's."""
    module, params, prefill, decode, _ = programs
    parked = POSITIONS

    def serve(cache, ids):
        done, toks = 0, []
        while done < len(ids):
            n = min(CHUNK, len(ids) - done)
            batch = np.zeros((4, CHUNK), np.int32)
            batch[1, :n] = ids[done:done + n]
            cache, out = prefill(params, cache, _int(parked, done, parked, parked),
                                 jnp.asarray(batch), _int(0, n - 1, 0, 0))
            done += n
        toks.append(int(out[1]))
        for step in range(4):
            cache, out = decode(params, cache, _int(parked, len(ids) + step, parked, parked))
            toks.append(int(out[1]))
        return cache, toks

    first, second = ids_of(1, 75, seed=8)[0], ids_of(1, 21, seed=9)[0]
    used, _ = serve(make_slot_cache(module, 4), first)
    rings = leaves_named(used, RING_LEAVES)
    assert all(np.abs(np.asarray(r)[1]).min(axis=(0, 1)).all() for r in rings)   # every place written
    _, after_a_tenant = serve(used, second)
    _, on_a_fresh_cache = serve(make_slot_cache(module, 4), second)
    assert after_a_tenant == on_a_fresh_cache
    want = reference(params, np.concatenate([second, on_a_fresh_cache[:-1]])[None].astype(np.int32),
                     sizes_of(module.config, 4))
    assert on_a_fresh_cache == np.asarray(want)[0, 20:].argmax(axis=-1).tolist()


def test_the_ring_mask_reads_a_window_and_nothing_before_zero():
    q = jnp.asarray([0, 5, 16, 17, 40, 70])
    got = np.asarray(package.ring_mask(q, jnp.arange(RING), RING, WINDOW))
    for row, t in zip(got, [0, 5, 16, 17, 40, 70]):
        want = {p % RING for p in range(max(t - WINDOW + 1, 0), t + 1)}
        assert set(np.flatnonzero(row).tolist()) == want


def test_a_ring_write_wraps_and_a_dead_slot_writes_nothing():
    leaf = jnp.zeros((3, 1, 2, 8))
    upd = jnp.arange(3 * 4 * 1 * 2, dtype=jnp.float32).reshape(3, 4, 1, 2) + 1
    out, = ring_pool_append([leaf], [upd], _int(6, 13, 128), jnp.asarray([True, True, False]))
    out = np.asarray(out)
    assert not out[2].any()
    for slot, pos in ((0, 6), (1, 13)):
        for j in range(4):
            np.testing.assert_array_equal(out[slot, 0, :, (pos + j) % 8], np.asarray(upd)[slot, j, 0])


@pytest.mark.parametrize("length", [1, 256, 200])
@pytest.mark.parametrize("on_tpu", [False, True])
def test_both_ring_writes_are_the_plain_loop_at_the_cells_ring(monkeypatch, on_tpu, length):
    """The chip's two in-place writes (plain XLA: they run here) and the
    scatter, at the cell's ring of 768 and chunk of 256 (two pieces of 128; 200
    is a ragged last one): pieces that run over the ring's end, one that ends
    on it, a slot at 0, a parked slot at the full pools' sentinel."""
    from deepspeed_tpu.ops.pallas import backend
    monkeypatch.setattr(backend, "on_tpu", lambda: on_tpu)
    ring, slots = 768, 6
    pos = [700, 5000, 767 + 3 * 768, 0, 32768, 768 - length]
    live = [True, True, True, True, False, True]
    keys = jax.random.split(jax.random.PRNGKey(length), 4)
    leaves = [jax.random.normal(keys[0], (slots, 5, ring)),
              jax.random.normal(keys[1], (slots, 2, 3, ring)).astype(jnp.bfloat16)]
    updates = [jax.random.normal(keys[2], (slots, length, 5)),
               jax.random.normal(keys[3], (slots, length, 2, 3)).astype(jnp.bfloat16)]
    got = ring_pool_append(leaves, updates, _int(*pos), jnp.asarray(live))
    for leaf, upd, out in zip(leaves, updates, got):
        want = np.array(leaf.astype(jnp.float32))
        upd = np.asarray(upd.astype(jnp.float32))
        for s in range(slots):
            for j in range(length if live[s] else 0):
                want[s, ..., (pos[s] + j) % ring] = upd[s, j]
        np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)), want)


# ---------------------------------------------------------------------------
# the kernels against XLA's forms (interpret mode off the chip)
# ---------------------------------------------------------------------------
def test_the_index_kernels_are_xlas_two_einsums():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    b, j, d, positions, l = 3, 4, 16, 64, 16
    pool = jax.random.normal(keys[0], (b, d, positions))
    q = jax.random.normal(keys[1], (b, l, j, d))
    w = jax.random.normal(keys[2], (b, l, j))
    lengths = _int(1, 40, 64)
    got = sparse_index.index_scores_decode(q[:, 0], w[:, 0], pool, lengths, block=16)
    want = package.index_scores(q[:, :1], w[:, :1], pool)[:, 0]
    for s, n in enumerate((16, 48, 64)):        # written up to the block of the last live key
        np.testing.assert_allclose(got[s, :n], want[s, :n], atol=1e-5)
    chunk = sparse_index.index_scores_chunk(q[1].reshape(l, -1), w[1], pool, 1, 3, block=16)
    np.testing.assert_allclose(chunk[:, :48], package.index_scores(q[1], w[1], pool[1])[:, :48],
                               atol=1e-5)
    assert sparse_index.chunk_tile(512) == 128 and sparse_index.chunk_tile(12) == 0
    blocks, size = sparse_index.chunk_blocks(jnp.int32(2049), 32768)
    assert (int(blocks), size) == (3, 1024)


def test_the_selected_decode_kernel_is_the_softmax_over_the_chosen():
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    b, heads, rank, dr, positions = 3, 4, 32, 8, 64
    pool = jax.random.normal(keys[0], (b, rank + dr, positions))
    q_lat = jax.random.normal(keys[1], (b, heads, rank))
    q_rope = jax.random.normal(keys[2], (b, heads, dr))
    chosen = jax.random.bernoulli(keys[3], 0.4, (b, positions)).at[:, 0].set(True)
    lengths = _int(0, 37, 64)
    got = latent_decode.latent_decode(q_lat, q_rope, pool, lengths, scale=0.2, block=16,
                                      chosen=chosen)
    want = package._mix_whole_pool(q_lat, q_rope, pool, lengths, 0.2, chosen)
    np.testing.assert_allclose(got[1:], want[1:], atol=2e-5)
    assert not np.asarray(got[0]).any()


def test_the_walk_kernel_is_the_expanded_walk_under_a_mask():
    from deepspeed_tpu.ops.pallas import latent_walk
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    b, l, heads, dn, dr, dv, rank, positions = 3, 16, 4, 16, 8, 16, 32, 64
    pool = jax.random.normal(keys[0], (b, rank + dr, positions))
    q_nope = jax.random.normal(keys[1], (b, l, heads, dn))
    q_rope = jax.random.normal(keys[2], (b, l, heads, dr))
    w_kvb = jax.random.normal(keys[3], (rank, heads, dn + dv)) * rank ** -0.5
    start, fed = _int(20, 0, 64), _int(16, 9, 0)
    at = start[:, None] + jnp.arange(l)[None]
    may = (jax.random.bernoulli(keys[4], 0.5, (b, l, positions)).at[:, :, 0].set(True)
           & (jnp.arange(positions)[None, None] <= at[..., None]))

    def allow(s, q_pos):
        mine = jax.lax.dynamic_index_in_dim(may, s, 0, keepdims=False)
        return (), lambda j, k_at, state: (jax.lax.dynamic_slice(mine, (0, j * 16), (l, 16)), state)

    want = package.expanded_walk(q_nope, q_rope, pool, w_kvb, start, fed, 16, allow)
    for s, real in ((0, 16), (1, 9)):
        blocks, block = latent_walk.walk_blocks(start[s] + fed[s], positions, 16)
        got = latent_walk.selected_walk(
            jnp.moveaxis(q_nope[s], 1, 0), jnp.moveaxis(q_rope[s], 1, 0),
            jnp.swapaxes(w_kvb, 0, 1), pool, may[s].astype(jnp.float32), s, blocks,
            scale=(dn + dr) ** -0.5, block=block, group=2)
        np.testing.assert_allclose(jnp.moveaxis(got, 0, 1)[:real], want[s, :real], atol=2e-6)
    assert latent_walk.takes(256, 128, 32768) and not latent_walk.takes(12, 128, 32768)


def _selection(case):
    """``(scores [rows, positions], bound [rows], n_blocks [tiles], k, block)``
    of one case of the selection kernel's test."""
    rng = np.random.default_rng(len(case))
    rows, positions, block, k = 16, 512, 128, 40
    scores = rng.standard_normal((rows, positions)).astype(np.float32)
    bound, n_blocks = 300 + np.arange(rows), [3]
    if case == "fewer_valid_than_k":
        bound = 3 + np.arange(rows)
        n_blocks = [1]
    elif case == "exactly_k":
        bound = np.full(rows, k)
        n_blocks = [1]
    elif case == "more_tied_at_the_bar_than_the_quota":
        # 30 above a bar that 25 share; a row of one value; a row where the
        # tied are exactly the quota
        scores = -rng.random((rows, positions))
        scores[:, 7:300:10], scores[:, 3:253:10] = 9.0, 1.0
        scores[1] = 0.5
        scores[2, :] = -1.0
        scores[2, 100:140] = 2.0
    elif case == "both_signs_and_both_zeros":
        scores = rng.choice(np.asarray([-2.5, -0.0, 0.0, 1e-30, -1e-30, 3.25], np.float32),
                            (rows, positions))
        scores[0] = -np.abs(rng.standard_normal(positions))        # all below zero
        scores[1] = np.where(np.arange(positions) % 2, -0.0, 0.0)  # the zeros alone
    elif case == "causal_bound_inside_a_block":
        bound, n_blocks = 250 + np.arange(rows), [3]               # 256 falls among the rows
    elif case == "garbage_past_the_written_blocks":
        n_blocks, bound = [2], 200 + 8 * np.arange(rows)           # rows reach past block 2
        scores[:, 256:] = rng.choice(np.asarray([np.nan, 3e38, -3e38, np.inf], np.float32),
                                     (rows, 256))
    elif case == "chunk_256_at_extent_32768":
        rows, positions, block, k = 256, 32768, 1024, 2048
        scores = np.round(rng.standard_normal((rows, positions)) * 64).astype(np.float32) / 64
        scores[:, 5120:] = np.nan                                  # never written
        bound = 4700 + np.arange(rows)
        n_blocks = [5] * 7 + [0]                                   # the last tile: no real row
    elif case == "a_row_a_slot_with_a_length_each":
        rows, k = 8, 24
        scores = np.round(scores[:rows] * 4) / 4
        bound, n_blocks = np.asarray([0, 1, 23, 24, 25, 128, 257, 384]), [3]
    else:
        assert case == "a_small_case"
        rows, positions, block, k = 8, 128, 128, 5
        scores, bound, n_blocks = scores[:rows, :positions], 1 + np.arange(rows) * 9, [1]
    return np.asarray(scores, np.float32), np.asarray(bound, np.int32), \
        np.asarray(n_blocks, np.int32), k, block


@pytest.mark.parametrize("case", [
    "fewer_valid_than_k", "exactly_k", "more_tied_at_the_bar_than_the_quota",
    "both_signs_and_both_zeros", "causal_bound_inside_a_block", "garbage_past_the_written_blocks",
    "chunk_256_at_extent_32768", "a_row_a_slot_with_a_length_each", "a_small_case"])
def test_the_selection_kernel_is_the_bar_and_the_mask(case):
    """``sparse_select.select_top_k`` (interpreted) against ``kth_largest`` +
    ``chosen_of``, mask for mask over the blocks it was given, whatever lies
    in the others."""
    from deepspeed_tpu.ops.pallas import sparse_select
    scores, bound, n_blocks, k, block = _selection(case)
    rows, positions = scores.shape
    tile = sparse_select.row_tile(rows)
    assert len(n_blocks) == rows // tile
    got = np.asarray(sparse_select.select_top_k(jnp.asarray(scores), jnp.asarray(bound),
                                                jnp.asarray(n_blocks), k, block=block))
    written = np.arange(positions)[None, :] < np.repeat(n_blocks, tile)[:, None] * block
    valid = (np.arange(positions)[None, :] < bound[:, None]) & written
    want, _ = package.chosen_of(*package.kth_largest(
        jnp.asarray(np.where(valid, scores, 0.0)), jnp.asarray(valid), k))
    want = np.asarray(want)
    assert set(np.unique(got[written])) <= {0.0, 1.0}
    np.testing.assert_array_equal(got[written] > 0, want[written])
    # what the reference chooses: k of a row, or every valid position
    np.testing.assert_array_equal(want.sum(-1), np.minimum(valid.sum(-1), k))
    if case == "more_tied_at_the_bar_than_the_quota":
        assert want[0, 3:103:10].all() and not want[0, 103:253:10].any()     # the lower ten of 25
        assert want[2, 100:140].all()
        assert want[1, :k].all() and not want[1, k:].any()
    assert sparse_select.takes(256, 32768) and sparse_select.takes(32, 32768)
    assert not sparse_select.takes(4, 32768) and not sparse_select.takes(16, 600)


@pytest.mark.parametrize("tick", ["prefill", "decode"])
def test_an_indexed_layer_on_the_chips_path_is_the_layer_on_xlas(programs, monkeypatch, tick):
    """One indexed attention layer over a serving cache, traced once as the
    chip runs it (scores, selection and attention as kernels, a fed slot at a
    time in a chunk and every slot at once in a decode step; interpreted here)
    and once as XLA's loops: the same outputs, the same chosen sets, the same
    rows written."""
    from deepspeed_tpu.inference.serving import programs as serving
    from deepspeed_tpu.ops.pallas import backend
    module = programs[0]
    cfg = module.config

    class OneLayer(nn.Module):
        @nn.compact
        def __call__(self, x, decode=True):
            index = self.variable("cache", "position_index", lambda: jnp.zeros([], jnp.int32))
            length = self.variable("cache", "chunk_length", lambda: jnp.zeros([], jnp.int32))
            fed = length.value if index.value.ndim else None
            return package.LatentAttention(cfg, cfg.kind_of(1), name="self_attn")(
                x.astype(jnp.float32) if x.ndim == 3 else
                jax.nn.one_hot(x, cfg.hidden_size), decode, fed)

    layer = OneLayer()
    params = jax.tree.map(lambda p: p * 3.0 if p.ndim >= 2 else p, nn.meta.unbox(layer.init(
        jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32), decode=False)["params"]))
    cache, _ = serving.without_next_tokens(make_slot_cache(layer, 8))    # a tile of the selection
    rng = np.random.default_rng(6)
    cache = jax.tree.map(lambda leaf: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
                         if leaf.ndim == 4 else leaf, cache)
    l = CHUNK if tick == "prefill" else 1
    x = jnp.asarray(rng.normal(size=(8, l, cfg.hidden_size)), jnp.float32)
    start = _int(POSITIONS, 70, 0, 33, 96, POSITIONS, 64, 16)
    fed = _int(0, l, min(l, 9), l, min(l, 3), 0, l, 1)

    def run():
        held = serving.with_write_positions(cache, start, fed)
        out, state = layer.apply({"params": params, "cache": held}, x,
                                 mutable=["cache", "intermediates"])
        return out, state["cache"], state["intermediates"]["self_attn"]["dsa_chosen"][0]

    want = jax.jit(lambda: run())()     # a program each: the second is traced under the patch
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "interpret_default", lambda: True)
    got = jax.jit(lambda: run())()
    real = np.asarray(fed) > 0
    np.testing.assert_allclose(np.asarray(got[0])[real], np.asarray(want[0])[real], atol=3e-5)
    assert not np.asarray(got[0])[0].any() or tick == "decode"      # a parked slot: zeros
    np.testing.assert_array_equal(np.asarray(got[2])[real], np.asarray(want[2])[real])
    for name in ("cached_latent", "cached_index_key"):
        np.testing.assert_array_equal(got[1]["self_attn"][name], want[1]["self_attn"][name])
    np.testing.assert_array_equal(got[1]["self_attn"]["sparse_reads"][1:3],
                                  want[1]["self_attn"]["sparse_reads"][1:3])
    # the selection's scores: by XLA the whole extent a real row, in the kernel
    # the live blocks (here the one of 128) of a tile of 16 queries or 8 slots
    at = SPARSE_READS.index("dsa_select_positions_read")
    assert int(want[1]["self_attn"]["sparse_reads"][at]) == real.sum() * l * POSITIONS
    assert int(got[1]["self_attn"]["sparse_reads"][at]) == (real.sum() * l if tick == "prefill"
                                                            else 8) * POSITIONS


# ---------------------------------------------------------------------------
# the chip's share; the scheduler
# ---------------------------------------------------------------------------
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(whole):
    """The guide's test of a share: the four quarters' expert layers, the
    shared expert and the residual counted once, add up to the uncut layer."""
    module, params = whole
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, module.config.hidden_size))

    def layer(held):
        block = DeepseekV3Block(build(held).config, True, 1)
        p = params["layers_1"] if held is None else held_params(params, *held)["layers_1"]
        return block.apply({"params": p}, x)

    uncut = layer(None)
    parts = [layer((first, QUARTER)) for first in range(0, EXPERTS, QUARTER)]
    # each part carries the attention's output, the residual and the shared expert
    np.testing.assert_allclose(sum(parts) - 3 * layer_without_routed(whole, x), uncut, atol=2e-5)


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
    module, params = build((4, 4)), held_params(whole[1], 4, 4)
    return deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                        max_out_tokens=POSITIONS,
                                        topology=MeshTopology(devices=jax.devices()[:1]))


def _scheduler(engine, **knobs):
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=4, page_size=16, kv_quant=False, prefill_chunk=CHUNK, prefill_interleave=2,
        **{"prefix_cache": "off", **knobs}))


def test_the_scheduler_serves_it_and_counts_what_the_layers_read(engine):
    sched = _scheduler(engine)
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
    want = np.asarray(reference(engine.params, ids, sizes_of(engine.module.config, 4)))
    for r, row, logits in zip(reqs, fed, want):
        assert list(r.output) == logits[len(r.prompt) - 1:len(row)].argmax(-1).tolist()
    counted = {k: v - before.get(k, 0) for k, v in trace.recorder().counters.items()}
    tokens = sum(len(p) for p in prompts)
    # two indexed layers: a query at t attends min(t + 1, TOP_K) of t + 1
    assert counted["dsa_positions_live_prefill"] == 2 * sum(n * (n + 1) // 2 for n in map(len, prompts))
    assert counted["dsa_positions_selected_prefill"] == 2 * sum(
        sum(min(t + 1, TOP_K) for t in range(len(p))) for p in prompts)
    assert 0 < counted["dsa_positions_selected_decode"] < counted["dsa_positions_live_decode"]
    assert counted["dsa_index_keys_read_prefill"] >= counted["dsa_positions_live_prefill"] // 90
    assert 0 < counted["swa_ring_positions_live_decode"] <= counted["swa_ring_positions_read_decode"]
    assert 0 < counted["swa_ring_positions_live_prefill"] <= counted["swa_ring_positions_read_prefill"]
    wide = engine.module.config
    assert counted["dsa_latent_bytes_written"] >= 2 * tokens * wide.latent_width * 4
    assert counted["dsa_index_key_bytes_written"] >= 2 * tokens * wide.index_head_dim * 4
    assert counted["swa_ring_bytes_written"] >= 2 * tokens * (48 + 8) * 4
    assert len(SPARSE_READS) == 9


@pytest.mark.parametrize("what", ["prefix_cache", "speculation", "migration"])
def test_what_copies_rows_by_position_refuses_a_ring_by_name(engine, what):
    from deepspeed_tpu.inference.serving.config import SpeculationConfig
    from deepspeed_tpu.inference.serving.scheduler import MigrationError
    if what == "prefix_cache":
        with pytest.raises(NotImplementedError, match="cached_window_latent"):
            _scheduler(engine, prefix_cache="on")
    elif what == "speculation":
        with pytest.raises(NotImplementedError, match="cached_window_latent"):
            _scheduler(engine, speculation=SpeculationConfig(enabled=True))
    else:
        sched = _scheduler(engine)
        with pytest.raises(MigrationError, match="cached_window_latent"):
            sched.export_inflight()
