"""Laguna-XS.2 as a configuration of ``models/llama.py``, at the
``laguna-test`` preset on seeded weights against the plain reference
(``benchmarks/reference/laguna.py``): the full forward pass; chunked prefill
then decode through the slot cache past several wraps of the ring; the ring
against a pool of every position under the window's mask; YaRN's frequencies
against numbers worked by hand; the gate; the route; a rung's rows."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import laguna as family
from benchmarks.reference import laguna as ref
from deepspeed_tpu.inference.serving.programs import (has_ring, make_apply_fn, make_slot_cache,
                                                      rows_of_slots, rows_to_slots,
                                                      slot_capacity, with_write_positions,
                                                      without_next_tokens)
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.common import (KV_READS, RING_KV_LEAVES, ring_mask,
                                         window_ring_positions)
from deepspeed_tpu.models.llama import LlamaForCausalLM, RopeKind, get_llama_config

LENGTH, PROMPT, CHUNK = 60, 44, 8
SIZES = ref.Sizes(
    layer_types=("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",),
    head_dim=16, window=8, top_k=2, routed_scale=2.5, eps=1e-6,
    rope_full=ref.Rope(500000.0, 0.5, 64.0, 16, 64.0, 1.0, 1.4158883083359672),
    rope_sliding=ref.Rope(10000.0))


@pytest.fixture(scope="module")
def built():
    """``(model, params, ids [2, 60], the reference's logits)``."""
    model = LlamaForCausalLM(get_llama_config("laguna-test"))
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0, 256))
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids[:, :8])["params"])
    want = jax.jit(ref.forward, static_argnums=2)(family.to_reference(params), ids, SIZES)
    return model, params, ids, np.asarray(want)


@pytest.fixture(scope="module")
def through_rings(built):
    """``{kv_quant: (logits, the cache after)}`` of :func:`served` over rings of 16."""
    model, params, ids, _ = built
    return {kv_quant: served(model, params, ids, without_next_tokens(
        make_slot_cache(model, 3, kv_quant=kv_quant))[0]) for kv_quant in (False, True)}


def served(model, params, ids, cache, rows=None):
    """Logits [2, 60, V] of the two sequences through a slot cache: sequence 0
    in slot 0, slot 1 parked, sequence 1 in slot 2 a chunk later; ragged last
    chunks; then a token a tick. ``rows``: through a rung's rows of the cache."""
    cap = slot_capacity(cache)
    step = make_apply_fn(model)

    @jax.jit
    def tick(cache, pos, toks, fed):
        fed_cache = with_write_positions(cache, pos, fed)
        if rows is None:
            return step(params, fed_cache, toks)
        logits, ran = step(params, rows_of_slots(fed_cache, rows), toks)
        return logits, rows_to_slots(cache, ran, rows)

    got = np.zeros((2, LENGTH, 256), np.float32)
    done, first = [0, 0], True
    while min(done) < PROMPT:
        toks, fed, pos = np.zeros((3, CHUNK), np.int32), np.zeros(3, np.int32), np.full(3, cap)
        for seq, slot in ((0, 0), (1, 2)):
            if done[seq] < PROMPT and not (first and seq):
                n = min(CHUNK, PROMPT - done[seq])
                toks[slot, :n], fed[slot], pos[slot] = ids[seq, done[seq]:done[seq] + n], n, done[seq]
        logits, cache = tick(cache, jnp.asarray(pos, jnp.int32), toks, jnp.asarray(fed))
        for seq, slot in ((0, 0), (1, 2)):
            got[seq, done[seq]:done[seq] + fed[slot]] = logits[slot, :fed[slot]]
            done[seq] += fed[slot]
        first = False
    for t in range(PROMPT, LENGTH):
        toks = np.zeros((3, 1), np.int32)
        toks[0, 0], toks[2, 0] = ids[0, t], ids[1, t]
        logits, cache = tick(cache, jnp.asarray([t, cap, t], jnp.int32), toks,
                             jnp.asarray([1, 0, 1], jnp.int32))
        got[0, t], got[1, t] = logits[0, 0], logits[2, 0]
    return got, cache


def test_the_full_forward_pass_is_the_references(built):
    model, params, ids, want = built
    got, _ = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    # both head counts are in the tree, over the same two key heads
    shapes = [params[f"layers_{i}"]["self_attn"]["q_proj"]["kernel"].shape for i in range(5)]
    assert shapes == [(64, 6, 16), (64, 8, 16), (64, 8, 16), (64, 8, 16), (64, 6, 16)]
    assert params["layers_1"]["self_attn"]["gate_proj"]["kernel"].shape == (64, 8)
    assert "mlp" in params["layers_0"] and "moe" in params["layers_1"]


@pytest.mark.parametrize("kv_quant, atol", [(False, 2e-6), (True, 0.15)], ids=["fp", "int8"])
def test_chunked_prefill_then_decode_over_a_wrapped_ring(built, through_rings, kv_quant, atol):
    """60 positions through rings of 16 (three wraps and more), a parked slot
    between the two sequences: the logits of every position against the
    reference's full pass. Over int8 pools the median position is off by a
    few thousandths; the most a rounding can do is hand a token another expert."""
    model, params, ids, want = built
    cache, _ = without_next_tokens(make_slot_cache(model, 3, kv_quant=kv_quant))
    assert has_ring(cache) and slot_capacity(cache) == 128
    ring = cache["layers_1"]["self_attn"][RING_KV_LEAVES[0]]
    assert ring.shape == (3, 2, 16, 16) and ring.dtype == (jnp.int8 if kv_quant else jnp.float32)
    assert ("cached_window_key_scale" in cache["layers_1"]["self_attn"]) == kv_quant
    got, after = through_rings[kv_quant]
    err = np.abs(got - want).max(axis=-1)
    assert err.max() < atol and np.median(err) < max(atol / 20, 2e-6)
    # the parked slot's ring was never written, whatever its sentinel folds to
    assert not np.asarray(after["layers_1"]["self_attn"][RING_KV_LEAVES[0]][1]).any()
    # the last decode tick: two live slots of 60 positions read together as far as
    # the longer goes (a block of 16 x 4 x 3 slots); of a ring, its 16 and the window's 8
    counts = dict(zip(KV_READS, np.asarray(after["layers_0"]["self_attn"]["kv_reads"])))
    assert (counts["kv_full_positions_read"], counts["kv_full_positions_live"]) == (192, 120)
    counts = dict(zip(KV_READS, np.asarray(after["layers_2"]["self_attn"]["kv_reads"])))
    assert (counts["kv_ring_positions_read"], counts["kv_ring_positions_live"]) == (48, 16)
    assert counts["kv_ring_bytes_written"] == 2 * 2 * 2 * (16 + 4 * kv_quant) * (1 if kv_quant else 4)


def test_a_rungs_rows_are_the_whole_programs(built, through_rings):
    """Three sequences of a cache of five slots, named by ``cache_slots``: the
    pools and the rings are written and read by row, the other slots' rows
    come back as they went in."""
    model, params, ids, want = built
    whole, _ = without_next_tokens(make_slot_cache(model, 5, kv_quant=True))
    marked = jax.tree.map(lambda leaf: leaf + 1 if leaf.ndim == 4 else leaf, whole)
    rows = jnp.asarray([3, 0, 4], jnp.int32)
    got, after = served(model, params, ids, marked, rows=rows)
    np.testing.assert_allclose(got, through_rings[True][0], atol=1e-6)
    for name in ("layers_0", "layers_1"):
        for leaf in (v for k, v in after[name]["self_attn"].items() if v.ndim == 4):
            assert (np.asarray(leaf[1]) == 1).all() and (np.asarray(leaf[2]) == 1).all()


def test_the_ring_is_a_pool_of_every_position_under_the_windows_mask(built, through_rings):
    """``window_ring`` None: the window layers keep pools of the full extent
    (no ring leaf: what copies rows by position may) and attend the same."""
    _, params, ids, _ = built
    model = LlamaForCausalLM(get_llama_config("laguna-test", window_ring=None))
    cache, _ = without_next_tokens(make_slot_cache(model, 3, kv_quant=False))
    assert not has_ring(cache)
    np.testing.assert_allclose(served(model, params, ids, cache)[0], through_rings[False][0],
                               atol=2e-6)
    # what a query at 21 reads of a ring of 16 with a window of 8: positions 14..21
    seen = np.asarray(ring_mask(jnp.asarray([21]), jnp.arange(16), 16, 8))[0]
    assert sorted(np.flatnonzero(seen)) == sorted(p % 16 for p in range(14, 22))
    # a first tenant's rows are never read: a query at 2 sees places 0..2 alone
    assert list(np.flatnonzero(np.asarray(ring_mask(jnp.asarray([2]), jnp.arange(16), 16, 8))[0])) == [0, 1, 2]
    assert window_ring_positions(512, 256) == 768 and window_ring_positions(512, 512) == 1024
    with pytest.raises(ValueError, match="window_ring_positions"):
        short = LlamaForCausalLM(get_llama_config("laguna-test", window_ring=8))
        short.apply({"params": params, "cache": llama.init_cache(short, 2)}, ids[:, :8],
                    decode=True, mutable=["cache"])


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """Published: theta 500,000, 64 rotated dimensions of 128, factor 64 over
    4,096, beta_fast 64, beta_slow 1. The pair that makes b turns over 4,096
    positions is 64 ln(4096 / (2 pi b)) / (2 ln 500000): 5.64 for 64 turns
    (low 5), 15.80 for one (high 16); pairs 0..5 keep theta^(-i/32), pairs from
    16 on take it over 64, pair 10 is (5/11 divided + 6/11 plain)."""
    kind = RopeKind(theta=500000.0, rotary_share=0.5, yarn_factor=64.0, original_positions=4096,
                    beta_fast=64.0, beta_slow=1.0, attention_factor=1.4158883083359672)
    inv, factor = llama.rope_frequencies(kind, 128)
    assert inv.shape == (32,) and factor == pytest.approx(0.1 * np.log(64) + 1)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    assert inv[10] == pytest.approx(plain[10] * (6 / 11 + 5 / 11 / 64), rel=1e-6)
    assert inv[1] == pytest.approx(0.66360, rel=1e-4) and inv[31] == pytest.approx(4.7088e-8, rel=1e-3)
    np.testing.assert_allclose(inv, ref.yarn_inverse_frequencies(
        ref.Rope(500000.0, 0.5, 64.0, 4096, 64.0, 1.0, 1.4158883083359672), 128), rtol=1e-6)
    # without the factor given, YaRN's own: 0.1 ln(factor) + 1
    assert llama.rope_frequencies(RopeKind(yarn_factor=64.0), 128)[1] == pytest.approx(1.41589, rel=1e-5)
    # half a head turns, by cosine and sine times the factor; the other half passes
    x = jnp.ones((1, 3, 2, 128))
    turned = llama.rotate(x, jnp.asarray([[0, 1, 7]]), kind)
    np.testing.assert_array_equal(np.asarray(turned[..., 64:]), 1.0)
    np.testing.assert_allclose(np.asarray(turned[0, 0, 0, :64]), factor, rtol=1e-6)
    angle = 7 * inv[3]
    assert float(turned[0, 2, 1, 3]) == pytest.approx(factor * (np.cos(angle) - np.sin(angle)), rel=1e-5)
    assert float(turned[0, 2, 1, 35]) == pytest.approx(factor * (np.cos(angle) + np.sin(angle)), rel=1e-5)


def test_the_gate_scales_a_head_by_the_sigmoid_of_its_own_logit(built):
    """Layer 0's attention with the gate's kernel zeroed is half the ungated
    attention (sigmoid 0), and with one head's column made large that head
    alone comes through whole."""
    model, params, ids, _ = built
    cfg = model.config
    attn = params["layers_0"]["self_attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64))
    run = lambda c, p: llama.LlamaAttention(c, 0).apply({"params": p}, x)  # noqa: E731
    plain = {k: v for k, v in attn.items() if k != "gate_proj"}
    ungated = run(get_llama_config("laguna-test", attention_gate=None), plain)
    zeroed = dict(attn, gate_proj={"kernel": jnp.zeros((64, 6))})
    np.testing.assert_allclose(np.asarray(run(cfg, zeroed)), np.asarray(ungated) / 2, atol=1e-6)
    # the reference's gate on the same weights
    flat = family.to_reference(params)
    want = ref.attention(ref.block_params(flat, 0), x, SIZES, 0) - x
    normed = ref.rms_norm(x, params["layers_0"]["input_layernorm"]["weight"], 1e-6)
    np.testing.assert_allclose(np.asarray(llama.LlamaAttention(cfg, 0).apply({"params": attn}, normed)),
                               np.asarray(want), atol=2e-6)


def test_the_route_is_sigmoid_normalised_scaled_and_the_shared_expert_counted_once(built):
    """A sparse layer's feed-forward against the reference's, and by hand for
    one token: its two largest sigmoid scores over their sum times 2.5."""
    model, params, _, _ = built
    from deepspeed_tpu.moe import MoE
    cfg = model.config
    blk = params["layers_2"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64))
    layer = MoE(hidden_size=64, expert=llama.LlamaMLP(cfg, num_experts=8, width=32), num_experts=8,
                k=2, drop_tokens=False, norm_topk_prob=True, experts_held=(0, 8), score="sigmoid",
                routed_scale=2.5, shared_expert=llama.LlamaMLP(cfg, width=32))
    got, _, _ = layer.apply({"params": blk}, h)
    bp = ref.block_params(family.to_reference(params), 2)
    weights = np.asarray(ref.router(bp, h, SIZES))
    want = ref.experts(bp, h, weights) + ref.swiglu(h, bp["shared_gate"], bp["shared_up"],
                                                   bp["shared_down"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    scores = 1 / (1 + np.exp(-np.asarray(h[0, 0] @ bp["router"], np.float64)))
    top = np.sort(scores)[-2:]
    assert sorted(weights[0, 0][weights[0, 0] > 0]) == pytest.approx(sorted(top / top.sum() * 2.5), rel=1e-5)
    assert (weights > 0).sum(axis=-1).tolist() == [[2] * 5] * 2
    assert weights.sum(axis=-1) == pytest.approx(2.5, rel=1e-5)


def test_a_window_layer_decodes_without_a_ring_where_it_raised_by_name():
    """SmallThinker's window layers (trained, never served before) decode over
    a cache longer than their window: a pool of every position under the
    window's mask, the lockstep ``generate`` path against the forward pass."""
    cfg = get_llama_config("smallthinker-test", num_hidden_layers=2, moe_num_experts=0,
                           sliding_window_layout=(0, 1), rope_layout=(0, 1))
    model = LlamaForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0, 256)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids[:, :8])["params"])
    want = model.apply({"params": params}, ids)
    step = jax.jit(lambda cache, part: model.apply({"params": params, "cache": cache}, part,
                                                   decode=True, mutable=["cache"]))
    cache = llama.init_cache(model, 2)
    outs = []
    for part in (ids[:, :12],) + tuple(ids[:, t:t + 1] for t in range(12, 24)):
        logits, upd = step(cache, part)
        outs.append(logits)
        cache = upd["cache"]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(want), atol=5e-6)


def test_a_decode_ticks_walk_reads_the_stored_codes_as_far_as_the_longest_slot_goes():
    """Sixteen slots of uneven lengths over an int8 pool of 64 positions in
    blocks of 16: the slots are walked together as far as the longest goes
    (the count says so), a parked slot gives zeros, and every slot's output is
    dense grouped-query attention over its own live rows, dequantised."""
    rng = np.random.default_rng(0)
    b, kv, rep, d, places, block = 16, 2, 3, 8, 64, 16
    lengths = np.asarray([60, 3, 9, 1, 17, 20, 5, 0, 2, 2, 2, 2, 33, 48, 1, 16])
    keys, values = (rng.integers(-127, 128, (b, kv, d, places)).astype(np.int8) for _ in range(2))
    k_scale, v_scale = (rng.uniform(0.01, 0.02, (b, kv, places)).astype(np.float32) for _ in range(2))
    q = rng.normal(size=(b, 1, kv * rep, d)).astype(np.float32)
    q_pos = np.maximum(lengths - 1, 0)[:, None]
    got, read = llama.cached_attention(
        jnp.asarray(q), jnp.asarray(keys), jnp.asarray(k_scale), jnp.asarray(values),
        jnp.asarray(v_scale), jnp.asarray(q_pos), jnp.asarray((lengths > 0).astype(np.int32)),
        window=places, block=block)
    assert int(read) == 16 * 16 * 4                       # 60 positions in blocks of 16
    for s in range(b):
        if not lengths[s]:
            assert not np.asarray(got[s]).any()
            continue
        n = lengths[s]
        for h in range(kv * rep):
            k = keys[s, h // rep, :, :n] * k_scale[s, h // rep, :n]
            v = values[s, h // rep, :, :n] * v_scale[s, h // rep, :n]
            scores = q[s, 0, h] @ k / np.sqrt(d)
            weights = np.exp(scores - scores.max())
            np.testing.assert_allclose(np.asarray(got[s, 0, h]), v @ (weights / weights.sum()),
                                       rtol=2e-5, atol=2e-5)
