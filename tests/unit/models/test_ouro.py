"""Ouro's looped stack as a configuration of ``models/llama.py``, at the
``ouro-test`` preset (three layers, three passes, four heads) on seeded weights
against the plain reference (``benchmarks/reference/ouro.py``): the full
forward pass and the exit distribution; chunked prefill then decode through the
int8 per-slot cache, whole and through a rung's rows; a pass's pool holds that
pass's keys; the write position moves once a token; one pass is the stack
``llama.py`` had; lockstep ``generate``."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import ouro as family
from benchmarks.reference import ouro as ref
from deepspeed_tpu.inference.serving.programs import (counter_widths, make_apply_fn,
                                                      make_slot_cache, rows_of_slots,
                                                      rows_to_slots, slot_capacity, with_counters,
                                                      with_write_positions, without_next_tokens)
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.common import KV_READS, PASS_READS
from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config

LENGTH, PROMPT, CHUNK, PASSES = 40, 28, 8, 3
SIZES = ref.Sizes(n_head=4, passes=PASSES, eps=1e-6, rope_theta=1e6)
#: a chunk walks its stored pool a block at a time, as the cell's configuration has it
WALKED = dict(decode_key_block=16, decode_cache_len=64)


@pytest.fixture(scope="module")
def built():
    """``(model, params, ids [2, 40], the reference's logits, its exit pdf)``."""
    model = LlamaForCausalLM(get_llama_config("ouro-test", **WALKED))
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256))
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids[:, :8])["params"])
    # a gate that is not all a half
    params["exit_gate"] = {"kernel": params["exit_gate"]["kernel"] * 20,
                           "bias": jnp.asarray([0.3], jnp.float32)}
    want, pdf = jax.jit(ref.forward, static_argnums=(2, 3))(
        family.to_reference(params), ids, SIZES, True)
    return model, params, ids, np.asarray(want), np.asarray(pdf)


def served(model, params, ids, cache, rows=None):
    """Logits [2, 40, V] of the two sequences through a slot cache: sequence 0
    in slot 0, slot 1 parked, sequence 1 in slot 2 a chunk later; ragged last
    chunks; then a token a tick. ``rows``: through a rung's rows of the cache.
    Also the cache after, and what the last tick's counters read."""
    cap = slot_capacity(cache)
    step = make_apply_fn(model)

    @jax.jit
    def tick(cache, pos, toks, fed):
        fed_cache = with_write_positions(cache, pos, fed)
        if rows is None:
            logits, cache = step(params, fed_cache, toks)
        else:
            logits, ran = step(params, rows_of_slots(fed_cache, rows), toks)
            cache = rows_to_slots(cache, ran, rows)
        return logits, cache, with_counters(cache, jnp.zeros((0,), jnp.int32))

    got = np.zeros((2, LENGTH, 256), np.float32)
    done, first = [0, 0], True
    while min(done) < PROMPT:
        toks, fed, pos = np.zeros((3, CHUNK), np.int32), np.zeros(3, np.int32), np.full(3, cap)
        for seq, slot in ((0, 0), (1, 2)):
            if done[seq] < PROMPT and not (first and seq):
                n = min(CHUNK, PROMPT - done[seq])
                toks[slot, :n], fed[slot], pos[slot] = ids[seq, done[seq]:done[seq] + n], n, done[seq]
        logits, cache, _ = tick(cache, jnp.asarray(pos, jnp.int32), toks, jnp.asarray(fed))
        for seq, slot in ((0, 0), (1, 2)):
            got[seq, done[seq]:done[seq] + fed[slot]] = logits[slot, :fed[slot]]
            done[seq] += fed[slot]
        first = False
    for t in range(PROMPT, LENGTH):
        toks = np.zeros((3, 1), np.int32)
        toks[0, 0], toks[2, 0] = ids[0, t], ids[1, t]
        logits, cache, counted = tick(cache, jnp.asarray([t, cap, t], jnp.int32), toks,
                                      jnp.asarray([1, 0, 1], jnp.int32))
        got[0, t], got[1, t] = logits[0, 0], logits[2, 0]
    return got, cache, np.asarray(counted)


@pytest.fixture(scope="module")
def through_pools(built):
    """``{kv_quant: served(...)}`` over a cache of three slots."""
    model, params, ids, _, _ = built
    return {kv_quant: served(model, params, ids, without_next_tokens(
        make_slot_cache(model, 3, kv_quant=kv_quant))[0]) for kv_quant in (False, True)}


def test_the_full_forward_pass_and_the_exit_pdf_are_the_references(built):
    model, params, ids, want, want_pdf = built
    got, pdf = jax.jit(lambda p: model.apply({"params": p}, ids, return_exit_pdf=True))(params)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-6)
    np.testing.assert_allclose(np.asarray(pdf), want_pdf, atol=2e-6)
    assert pdf.shape == (2, LENGTH, PASSES)
    np.testing.assert_allclose(np.asarray(pdf).sum(axis=-1), 1.0, atol=1e-6)
    assert np.asarray(pdf).std() > 0.01
    # one set of weights: three layers' in the tree, four norms a layer, a gate
    assert sorted(params) == ["embed_tokens", "exit_gate", "layers_0", "layers_1", "layers_2",
                              "lm_head", "norm"]
    assert sorted(params["layers_0"]) == ["input_layernorm", "input_layernorm_2", "mlp",
                                          "post_attention_layernorm",
                                          "post_attention_layernorm_2", "self_attn"]
    assert params["exit_gate"]["kernel"].shape == (64, 1)
    # without the flag the logits alone, the same
    np.testing.assert_array_equal(np.asarray(model.apply({"params": params}, ids)), np.asarray(got))


@pytest.mark.parametrize("kv_quant, atol", [(False, 5e-6), (True, 0.1)], ids=["fp", "int8"])
def test_chunked_prefill_then_decode_through_the_per_pass_pools(built, through_pools, kv_quant, atol):
    """40 positions in chunks of 8 and then a token a tick, a parked slot
    between the two sequences: every position's logits against the reference's
    full pass. One set of leaves a layer, its pools three passes' heads wide."""
    model, params, ids, want, _ = built
    cache, _ = without_next_tokens(make_slot_cache(model, 3, kv_quant=kv_quant))
    leaves = cache["layers_1"]["self_attn"]
    assert leaves["cached_key"].shape == (3, PASSES * 4, 16, 64)
    assert leaves["cached_key"].dtype == (jnp.int8 if kv_quant else jnp.float32)
    assert ("cached_key_scale" in leaves) == kv_quant
    if kv_quant:
        assert leaves["cached_value_scale"].shape == (3, PASSES * 4, 64)
    assert slot_capacity(cache) == 64 and cache["chunk_length"].shape == (3,)
    got, after, counted = through_pools[kv_quant]
    err = np.abs(got - want).max(axis=-1)
    assert err.max() < atol and np.median(err) < max(atol / 10, 5e-6)
    # the parked slot's rows were never written
    assert not np.asarray(after["layers_1"]["self_attn"]["cached_key"][1]).any()
    # the last decode tick: two live slots of 40 positions read together as far as
    # the longer goes (three blocks of 16 x 3 slots), by each of three passes
    counts = dict(zip(KV_READS, np.asarray(after["layers_0"]["self_attn"]["kv_reads"])))
    assert (counts["kv_full_positions_read"], counts["kv_full_positions_live"]) == (
        PASSES * 144, PASSES * 80)
    by_pass = np.asarray(after["layers_0"]["self_attn"]["kv_pass_reads"]).reshape(PASSES, -1)
    assert by_pass.tolist() == [[144, 80]] * PASSES and len(PASS_READS) == 2
    # and what a program hands the host: summed over the layers, name by name
    assert counter_widths(after) == [("kv_reads", 5), ("kv_pass_reads", 2 * PASSES)]
    assert counted.tolist() == [3 * PASSES * 144, 3 * PASSES * 80, 0, 0, 0] + [3 * 144, 3 * 80] * PASSES


def test_a_pass_pool_holds_that_passs_keys_and_none_is_overwritten(built, through_pools):
    """Pass ``t``'s heads of layer 1's key pool are pass ``t``'s keys of the
    reference (RoPE'd, at every live position), the passes differ, and the
    index moved once a token."""
    model, params, ids, _, _ = built
    _, after, _ = through_pools[False]
    pool = np.asarray(after["layers_1"]["self_attn"]["cached_key"])        # [3, 12, 16, 64]
    flat = family.to_reference(params)

    @jax.jit
    def layer_1_keys(x, bp):
        with jax.default_matmul_precision("highest"):
            k = ref.rms_norm(x, bp["ln1"], 1e-6) @ bp["wk"]
        return ref.rope(k.reshape(2, LENGTH, 4, 16).transpose(0, 2, 1, 3), 1e6)

    h = ref.embed(flat, ids)
    for t in range(PASSES):
        x = h
        for i in range(3):
            bp = ref.block_params(flat, i)
            if i == 1:
                k = layer_1_keys(x, bp)
                for seq, slot in ((0, 0), (1, 2)):
                    np.testing.assert_allclose(pool[slot, 4 * t:4 * t + 4, :, :LENGTH],
                                               np.asarray(k[seq]).transpose(0, 2, 1), atol=5e-6)
            x = family._feed_forward(bp, family._attention(bp, x, SIZES), SIZES)
        h, _ = family._close_pass({k: flat[k] for k in ("norm", "gate_w", "gate_b")}, x, SIZES)
    assert np.abs(pool[0, :4] - pool[0, 4:8]).max() > 0.1
    assert np.abs(pool[0, 4:8] - pool[0, 8:]).max() > 0.1
    assert not pool[:, :, :, LENGTH:].any()
    # lockstep: one token, one step of the index, whatever the passes
    lock = llama.init_cache(model, 2)
    _, upd = model.apply({"params": params, "cache": lock}, ids[:, :5], decode=True,
                         mutable=["cache"])
    assert int(upd["cache"]["layers_2"]["self_attn"]["cache_index"]) == 5
    _, upd = model.apply({"params": params, "cache": upd["cache"]}, ids[:, 5:6], decode=True,
                         mutable=["cache"])
    assert int(upd["cache"]["layers_0"]["self_attn"]["cache_index"]) == 6


def test_a_rungs_rows_are_the_whole_programs(built, through_pools):
    """Three sequences of a cache of five slots, named by ``cache_slots``: the
    pools are written and read by row and by pass, the other slots' rows come
    back as they went in."""
    model, params, ids, _, _ = built
    whole, _ = without_next_tokens(make_slot_cache(model, 5, kv_quant=True))
    marked = jax.tree.map(lambda leaf: leaf + 1 if leaf.ndim == 4 else leaf, whole)
    rows = jnp.asarray([3, 0, 4], jnp.int32)
    got, after, _ = served(model, params, ids, marked, rows=rows)
    np.testing.assert_allclose(got, through_pools[True][0], atol=1e-6)
    for leaf in (v for v in after["layers_1"]["self_attn"].values() if v.ndim == 4):
        assert (np.asarray(leaf[1]) == 1).all() and (np.asarray(leaf[2]) == 1).all()


def test_the_chunk_through_whole_pools_is_the_walks(built, through_pools):
    """``decode_key_block`` None: a chunk takes its pass's heads of the whole
    pool to the attention backend (no ``chunk_length`` leaf); the same logits."""
    _, params, ids, _, _ = built
    model = LlamaForCausalLM(get_llama_config("ouro-test", decode_cache_len=64))
    cache, _ = without_next_tokens(make_slot_cache(model, 3, kv_quant=False))
    assert "chunk_length" not in cache
    np.testing.assert_allclose(served(model, params, ids, cache)[0], through_pools[False][0],
                               atol=5e-6)


def test_one_pass_is_the_stack_llama_had():
    """``loop_passes`` 1: the tree, the cache and the logits of the plain
    stack, sandwich norms or none; no gate, no exit distribution."""
    plain = LlamaForCausalLM(get_llama_config("test"))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 256)
    params = nn.meta.unbox(plain.init(jax.random.PRNGKey(0), ids)["params"])
    assert sorted(params) == ["embed_tokens", "layers_0", "layers_1", "lm_head", "norm"]
    assert sorted(params["layers_0"]) == ["input_layernorm", "mlp", "post_attention_layernorm",
                                          "self_attn"]
    cache = llama.init_cache(plain, 2)
    assert sorted(cache["layers_0"]["self_attn"]) == ["cache_index", "cached_key", "cached_value",
                                                      "kv_reads"]
    assert cache["layers_0"]["self_attn"]["cached_key"].shape == (2, 128, 2, 16)
    slots, _ = without_next_tokens(make_slot_cache(plain, 4, kv_quant=True))
    assert slots["layers_0"]["self_attn"]["cached_key"].shape == (4, 2, 16, 128)
    assert counter_widths(slots) == [("kv_reads", 5)]
    with pytest.raises(ValueError, match="no exit gate"):
        plain.apply({"params": params}, ids, return_exit_pdf=True)
    # a looped stack of one pass with the same weights (its norms ones, as drawn)
    one = LlamaForCausalLM(get_llama_config("ouro-test", loop_passes=1, num_hidden_layers=2,
                                            num_key_value_heads=2, sandwich_norm=False,
                                            rope_theta=10000.0))
    np.testing.assert_array_equal(np.asarray(one.apply({"params": params}, ids)),
                                  np.asarray(plain.apply({"params": params}, ids)))
    with pytest.raises(ValueError, match="loop_passes"):
        get_llama_config("ouro-test", loop_passes=0)


def test_lockstep_generate_is_the_forward_pass(built):
    """``generate``'s path: a prompt at once, then a token a call, over the
    lockstep cache (float pools, a scalar index)."""
    model, params, ids, want, _ = built
    step = jax.jit(lambda cache, part: model.apply({"params": params, "cache": cache}, part,
                                                   decode=True, mutable=["cache"]))
    cache = llama.init_cache(model, 2)
    outs = []
    for part in (ids[:, :12],) + tuple(ids[:, t:t + 1] for t in range(12, LENGTH)):
        logits, upd = step(cache, part)
        outs.append(logits)
        cache = upd["cache"]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), want, atol=5e-6)


@pytest.mark.parametrize("rep, rows", [(1, None), (3, [4, 1, 3])], ids=["mha_whole", "gqa_rung"])
def test_the_decode_kernel_reads_a_passs_heads_where_they_lie(rep, rows):
    """``ops/pallas/pool_decode.py`` (the interpreter off the chip) over int8
    pools that hold three passes' key heads side by side: pass ``t``'s call is
    the kernel over that pass's heads cut out, for both of its bodies, and
    XLA's loop (``cached_attention``) agrees."""
    from deepspeed_tpu.models.common import cached_attention
    from deepspeed_tpu.ops.pallas.pool_decode import pool_decode

    rng = np.random.default_rng(0)
    slots, kv, d, places, parts = 6, 2, 128, 256, 3
    held = np.asarray([0, 129, 256] if rows else [0, 1, 128, 129, 256, 200])
    keys, values = (jnp.asarray(rng.integers(-127, 128, (slots, parts * kv, d, places)), jnp.int8)
                    for _ in range(2))
    k_scale, v_scale = (jnp.asarray(rng.uniform(0.01, 0.02, (slots, parts * kv, places)),
                                    jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(held), kv * rep, d)), jnp.float32)
    q_pos = jnp.asarray(np.maximum(held - 1, 0), jnp.int32)
    fed = jnp.asarray(held > 0, jnp.int32)
    how = dict(window=places, block=128, rows=None if rows is None else jnp.asarray(rows))
    for t in range(parts):
        cut = lambda leaf: leaf[:, t * kv:(t + 1) * kv]  # noqa: E731
        want, read = pool_decode(q, cut(keys), cut(k_scale), cut(values), cut(v_scale), q_pos,
                                 fed, **how)
        got, read_t = jax.jit(lambda t: pool_decode(q, keys, k_scale, values, v_scale, q_pos, fed,
                                                    part=t, parts=parts, **how))(jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(read_t) == int(read)
        loop, _ = cached_attention(q[:, None], keys, k_scale, values, v_scale, q_pos[:, None], fed,
                                   part=jnp.int32(t), parts=parts, **how)
        np.testing.assert_allclose(np.asarray(loop[:, 0]), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[0]).any()


@pytest.mark.parametrize("length, rows, pos", [
    (1, None, [0, 5, 127, 128, 255, 256]), (1, [4, 1, 3], [130, 256, 7]),
    (5, [4, 1, 3], [126, 256, 253]), (128, None, [0, 5, 127, 128, 255, 256])],
    ids=["a_token", "a_token_a_rung", "a_short_piece_a_rung", "a_window"])
def test_the_write_kernel_puts_tokens_on_their_lanes_of_their_passs_heads(rows, pos, length):
    """``ops/pallas/pool_write.py`` (the interpreter off the chip) against the
    scatter it stands for on a TPU: int8 pools and bfloat16 scales that hold
    three passes' heads; a token or a piece a sequence at a window's first and
    last lane, across a boundary, over the extent's end (dropped) and past it
    (a parked slot: nothing written), through a rung's rows; and over pools of
    one pass."""
    from deepspeed_tpu.models import common
    from deepspeed_tpu.ops.pallas.pool_write import pool_write, takes

    rng = np.random.default_rng(0)
    slots, kv, d, places, parts, n = 6, 2, 128, 256, 3, len(pos)
    leaves = [jnp.asarray(rng.integers(-127, 128, (slots, parts * kv, d, places)), jnp.int8)
              for _ in range(2)] + [jnp.asarray(rng.uniform(0.01, 0.02, (slots, parts * kv, places)),
                                                jnp.bfloat16) for _ in range(2)]
    updates = [jnp.asarray(rng.integers(-127, 128, (n, length, kv, d)), jnp.int8) for _ in range(2)] \
        + [jnp.asarray(rng.uniform(0.1, 0.2, (n, length, kv)), jnp.bfloat16) for _ in range(2)]
    assert takes(leaves, updates)
    assert not takes(leaves, [jnp.repeat(u[:, :1], 129, axis=1) for u in updates])
    rows = None if rows is None else jnp.asarray(rows, jnp.int32)
    as_f32 = lambda t: np.asarray(t.astype(jnp.float32))  # noqa: E731
    for t in range(parts):
        how = dict(part=jnp.int32(t), parts=parts)
        want = common.slot_pool_append(leaves, updates, jnp.asarray(pos), rows, **how)
        got = pool_write(leaves, updates, jnp.asarray(pos), rows, **how)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(as_f32(g), as_f32(w))
        assert (as_f32(got[0]) != as_f32(leaves[0])).any()
    single = [leaf[:, :kv] for leaf in leaves]
    for g, w in zip(pool_write(single, updates, jnp.asarray(pos), rows),
                    common.slot_pool_append(single, updates, jnp.asarray(pos), rows)):
        np.testing.assert_array_equal(as_f32(g), as_f32(w))
