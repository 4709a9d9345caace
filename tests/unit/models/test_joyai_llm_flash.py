"""JoyAI-LLM-Flash's family (``models/deepseek_v3.py``) at the tiny preset on
the CPU: the package against the plain reference
(``benchmarks/reference/joyai_llm_flash.py``) on seeded weights, the absorbed
form of the latent attention against the expanded one, interleaved RoPE far
out, the router, the chip's share against the uncut layer, what a tick may
and may not touch of the latent pool, and latent rows in prefix blocks and
migration payloads."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import joyai_llm_flash as family
from benchmarks.reference import joyai_llm_flash as ref
from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler, Request, ServingConfig
from deepspeed_tpu.inference.serving.programs import (build_decode_step, build_prefill_step,
                                                      make_apply_fn, make_slot_cache,
                                                      slot_capacity)
from deepspeed_tpu.models.common import LATENT_LEAVES, init_cache
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Block, DeepseekV3ForCausalLM,
                                              absorbed_step, expanded_walk,
                                              get_deepseek_v3_config, rotate_interleaved)
from deepspeed_tpu.moe.sharded_moe import topkrouting
from deepspeed_tpu.utils import trace

EXPERTS, QUARTER = 16, 4


def one_device():
    from deepspeed_tpu.parallel.topology import MeshTopology
    return MeshTopology(devices=jax.devices()[:1])


def build(held=None, **overrides):
    cfg = get_deepseek_v3_config("deepseek-v3-test", experts_held=held, **overrides)
    return DeepseekV3ForCausalLM(cfg)


def sizes_of(cfg, first=0):
    return ref.Sizes(n_layer=cfg.num_hidden_layers, n_dense=cfg.first_k_dense_replace,
                     d_nope=cfg.qk_nope_head_dim, d_rope=cfg.qk_rope_head_dim,
                     rank=cfg.kv_lora_rank, top_k=cfg.num_experts_per_tok,
                     routed_scale=cfg.routed_scaling_factor, rope_theta=cfg.rope_theta,
                     experts_first=first, eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def whole():
    """The uncut model and its seeded weights (float32)."""
    module = build()
    params = nn.meta.unbox(jax.jit(module.init)(jax.random.PRNGKey(32),
                                                jnp.zeros((1, 8), jnp.int32))["params"])
    return module, params


def held_params(params, first, count):
    """The same weights with only experts ``[first, first + count)`` in each bank."""
    def cut(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        return leaf[first:first + count] if "deepspeed_experts" in names else leaf
    return jax.tree_util.tree_map_with_path(cut, params)


def ids_of(n, length, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n, length)).astype(np.int32)


def latent_leaves(cache):
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", None) in LATENT_LEAVES]


# ---------------------------------------------------------------------------
# the package against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("held", [None, (0, 4), (4, 4), (12, 4)], ids=str)
def test_full_forward_matches_the_reference(whole, held):
    module, params = whole
    first, count = held or (0, EXPERTS)
    mine = held_params(params, first, count)
    ids = ids_of(2, 37)                         # two whole key blocks of 16 and a ragged one
    # both sides jitted: eagerly each is dispatched an operation at a time
    got = jax.jit(build(held).apply)({"params": mine}, ids)
    sizes = sizes_of(module.config, first)
    want = jax.jit(lambda flat: ref.forward(flat, ids, sizes))(family.to_reference(mine))
    assert got.shape == (2, 37, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_lockstep_decode_matches_the_full_forward(whole):
    module, params = whole
    ids = ids_of(2, 21)
    full = jax.jit(module.apply)({"params": params}, ids)

    @jax.jit  # one program for the thirteen, one for each of the eight that follow
    def step(cache, fed):
        return module.apply({"params": params, "cache": cache}, fed, decode=True,
                            mutable=["cache"])

    out, upd = step(init_cache(module, 2), ids[:, :13])
    outs = [out]
    for t in range(13, 21):
        out, upd = step(upd["cache"], ids[:, t:t + 1])
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(full),
                               atol=2e-5)


@pytest.fixture(scope="module")
def served():
    """Six requests over four slots through chunked prefill (16-token chunks
    that end ragged, key blocks of 16) and decode, a quarter of the experts held."""
    held = (4, 4)
    module = build(held, decode_cache_len=64)
    whole_params = nn.meta.unbox(jax.jit(build().init)(jax.random.PRNGKey(32),
                                                       jnp.zeros((1, 8), jnp.int32))["params"])
    params = held_params(whole_params, *held)
    engine = deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                          max_out_tokens=64, topology=one_device())
    before = dict(trace.recorder().counters)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=4, page_size=8, kv_quant=False, prefill_chunk=16, prefill_interleave=2,
        prefix_cache="off"))
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, 256, (n,)).astype(np.int32), max_new_tokens=6)
            for n in (37, 13, 16, 45, 21, 5)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    counted = {k: v - before.get(k, 0) for k, v in trace.recorder().counters.items()}
    return module, params, held, sched, reqs, counted


@pytest.fixture(scope="module")
def served_reference_logits(served):
    """The reference's logits over every request's prompt and fed-back tokens,
    one pass over the six padded on the right to the longest: the reference
    is causal, so a row's logits up to its length are its own."""
    module, params, held, _, reqs, _ = served
    fed = [np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)]) for r in reqs]
    ids = np.stack([np.pad(row, (0, max(map(len, fed)) - len(row))) for row in fed])
    logits = np.asarray(ref.forward(family.to_reference(params), ids,
                                    sizes_of(module.config, held[0])))
    return [out[len(r.prompt) - 1:len(row)] for r, row, out in zip(reqs, fed, logits)]


@pytest.mark.parametrize("which", range(6))
def test_served_tokens_are_the_references_greedy_tokens(served, served_reference_logits, which):
    """Chunked prefill (the last chunk ragged) and absorbed decode through the
    slot cache against the reference's full forward, teacher-forced."""
    r = served[4][which]
    assert len(r.output) == 6
    logits = served_reference_logits[which]
    gap = logits.max(axis=-1) - logits[np.arange(6), np.asarray(r.output)]
    assert gap.max() < 1e-4


def test_serving_counts_latent_positions_and_rows(served):
    module, _, _, sched, reqs, counted = served
    cfg = module.config
    fed = sum(len(r.prompt) for r in reqs)
    tokens = fed + sum(len(r.output) - 1 for r in reqs)
    assert counted["latent_bytes_written"] == tokens * cfg.num_hidden_layers * 40 * 4
    # a decode tick's absorbed step reads every slot's whole pool
    assert counted["latent_positions_read_decode"] == (
        sched.ticks["decode"] * sched.slots * 64 * cfg.num_hidden_layers)
    # the walk reads whole key blocks up to each fed slot's live length
    assert (counted["latent_positions_live_prefill"] <= counted["latent_positions_read_prefill"]
            < counted["latent_positions_live_prefill"]
            + 16 * cfg.num_hidden_layers * sum(-(-len(r.prompt) // 16) for r in reqs))
    assert 0 < counted["latent_positions_live_decode"] < counted["latent_positions_read_decode"]
    # every real token takes k experts in each of the two expert layers
    assert counted["moe_rows_routed"] + counted["moe_rows_elsewhere"] == tokens * 4 * 2
    assert 0 < counted["moe_rows_routed"] <= counted["moe_rows_computed"]


# ---------------------------------------------------------------------------
# the two forms of the attention, and the rotation
# ---------------------------------------------------------------------------
def _attention_inputs(b=3, positions=64, heads=4, dn=16, dr=8, dv=16, rank=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = jax.random.normal(keys[0], (b, rank + dr, positions))
    w_kvb = jax.random.normal(keys[1], (rank, heads, dn + dv)) * rank ** -0.5
    q_nope = jax.random.normal(keys[2], (b, 1, heads, dn))
    q_rope = jax.random.normal(keys[3], (b, 1, heads, dr))
    return q_nope, q_rope, pool, w_kvb


def _materialised(q_nope, q_rope, pool, w_kvb, lengths):
    """Expanded attention of one query a sequence, every score at once."""
    rank, dn = w_kvb.shape[0], q_nope.shape[-1]
    kv = jnp.einsum("bcp,chd->bphd", pool[:, :rank], w_kvb)
    s = (jnp.einsum("bhd,bphd->bhp", q_nope[:, 0], kv[..., :dn])
         + jnp.einsum("bhd,bdp->bhp", q_rope[:, 0], pool[:, rank:])) / np.sqrt(dn + q_rope.shape[-1])
    s = jnp.where(jnp.arange(pool.shape[-1])[None, None, :] < lengths[:, None, None], s, -jnp.inf)
    return jnp.einsum("bhp,bphd->bhd", jax.nn.softmax(s, axis=-1), kv[..., dn:])


@pytest.mark.parametrize("lengths", [(1, 17, 64), (33, 16, 48)], ids=str)
def test_absorbed_is_expanded_at_float32(lengths):
    """One layer's attention, one query a slot over a pool: the absorbed step,
    the expanded walk (blocks of 16, bounded by each slot's length) and the
    materialised expanded form give the same numbers."""
    q_nope, q_rope, pool, w_kvb = _attention_inputs()
    lengths = jnp.asarray(lengths, jnp.int32)
    want = _materialised(q_nope, q_rope, pool, w_kvb, lengths)
    absorbed = absorbed_step(q_nope[:, 0], q_rope[:, 0], pool, w_kvb, lengths)
    walked = expanded_walk(q_nope, q_rope, pool, w_kvb, lengths - 1,
                           jnp.ones_like(lengths), 16)[:, 0]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(walked), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)], ids=["f32", "bf16"])
def test_the_decode_kernel_is_the_two_matmuls_over_the_whole_pool(dtype, atol):
    """``ops/pallas/latent_decode.py`` (interpreted here) against XLA's float32
    form of the same step (over a bfloat16 pool the kernel's queries and
    probabilities meet the pool in bfloat16, one MXU pass each): blocks of 128
    of 512 positions, lengths that end inside a block, on its edge, at one
    token, at the pool's end, and a parked slot (0), which gives zeros."""
    from deepspeed_tpu.models.deepseek_v3 import _mix_whole_pool
    from deepspeed_tpu.ops.pallas.latent_decode import latent_decode
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q_lat = jax.random.normal(keys[0], (5, 8, 64)).astype(dtype)
    q_rope = jax.random.normal(keys[1], (5, 8, 16)).astype(dtype)
    pool = jax.random.normal(keys[2], (5, 80, 512)).astype(dtype)
    lengths = jnp.asarray([1, 128, 300, 0, 512], jnp.int32)
    got = latent_decode(q_lat, q_rope, pool, lengths, scale=0.1, block=128)
    want = _mix_whole_pool(q_lat, q_rope, pool, lengths, 0.1)
    assert got.shape == (5, 8, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol)
    assert float(jnp.abs(got[3]).max()) == 0.0


def test_a_parked_slots_walk_is_empty():
    q_nope, q_rope, pool, w_kvb = _attention_inputs()
    out = expanded_walk(q_nope, q_rope, pool, w_kvb, jnp.asarray([5, 64, 9], jnp.int32),
                        jnp.asarray([1, 0, 1], jnp.int32), 16)
    assert float(jnp.abs(out[1]).max()) == 0.0 and float(jnp.abs(out[0]).max()) > 0.0


@pytest.mark.parametrize("first", [0, 4090, 16000])
def test_interleaved_rope_far_out_is_the_references(first):
    """theta 32 M over 64 dimensions at positions past 4,096: the package's
    rotation of the pairs (2i, 2i+1) against the reference's complex turn."""
    x = jax.random.normal(jax.random.PRNGKey(first), (2, 9, 3, 64))
    positions = first + jnp.arange(9)
    got = rotate_interleaved(x, jnp.broadcast_to(positions, (2, 9)), 32e6)
    want = ref.rope(jnp.moveaxis(x, 2, 1), positions, 32e6)          # [b, heads, l, d]
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 2, 1)), np.asarray(want), atol=1e-5)
    if first:
        assert float(jnp.abs(got - x).max()) > 0.1                   # it turned
    # one shared rope key, no head axis
    np.testing.assert_allclose(
        np.asarray(rotate_interleaved(x[:, :, 0], jnp.broadcast_to(positions, (2, 9)), 32e6)),
        np.asarray(got[:, :, 0]), atol=0)


# ---------------------------------------------------------------------------
# the router and the share
# ---------------------------------------------------------------------------
def test_the_router_is_the_references(whole):
    """Top-k by biased sigmoid scores; weights the unbiased scores of the
    chosen, normalised, times 2.5: the package's gate core against the
    reference's router on one layer's weights."""
    module, params = whole
    cfg = module.config
    bp = ref.block_params(family.to_reference(params), 1)
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 50, cfg.hidden_size)) * 3
    bias = jax.random.normal(jax.random.PRNGKey(3), (EXPERTS,)) * 0.3      # decides choices
    bp = dict(bp, router_bias=bias)
    want = np.asarray(ref.router(bp, h, sizes_of(cfg)))[0]                 # [50, experts]
    logits = jnp.einsum("sm,me->se", h[0], bp["router"])
    _, routing, _ = topkrouting(logits, cfg.num_experts_per_tok, 1.0, 4, drop_tokens=False,
                                normalize=True, score="sigmoid", select_bias=bias, scale=2.5)
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(routing.expert), np.asarray(routing.weight), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got.sum(axis=1), 2.5, atol=1e-5)
    unbiased = np.asarray(ref.router(dict(bp, router_bias=jnp.zeros(EXPERTS)), h, sizes_of(cfg)))[0]
    assert ((want > 0) != (unbiased > 0)).any()                            # the bias chose


def test_four_quarters_of_the_experts_add_up_to_the_uncut_layer(whole):
    """The routed parts of the four shares, with the shared expert (every
    chip's) counted once, are the uncut reference's layer; the package's
    block with a share held gives that share's part."""
    module, params = whole
    cfg = module.config
    bp = ref.block_params(family.to_reference(params), 1)                  # the first expert layer
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.hidden_size))
    after = ref.attention(bp, x, sizes_of(cfg))
    uncut = ref.experts(bp, after, sizes_of(cfg))
    h = ref.rms_norm(after, bp["ln2"], cfg.rms_norm_eps)
    weights = ref.router(bp, h, sizes_of(cfg))
    shared = ref.swiglu(h, bp["shared_gate"], bp["shared_up"], bp["shared_down"])
    parts = []
    for q in range(EXPERTS // QUARTER):
        first = q * QUARTER
        mine = dict(bp, **{k: bp[k][first:first + QUARTER] for k in ("w_gate", "w_up", "w_down")})
        parts.append(ref.routed(mine, h, weights, sizes_of(cfg, first)))
        layer = DeepseekV3Block(build((first, QUARTER)).config, True)
        got = layer.apply({"params": held_params(params, first, QUARTER)["layers_1"]}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(after + parts[-1] + shared),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(after + sum(parts) + shared), np.asarray(uncut),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# what a tick may touch of the latent pool
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def programs(whole):
    module, params = whole
    module = build(decode_cache_len=64)
    apply_fn = make_apply_fn(module)
    prefill = jax.jit(build_prefill_step(apply_fn, False, 1.0, 0, 1.0))
    decode = jax.jit(build_decode_step(apply_fn, False, 1.0, 0, 1.0))
    return module, params, prefill, decode


def _int(*values):
    return np.asarray(values, np.int32)


def _rows(cache, slot):
    return [np.asarray(leaf[slot]) for leaf in latent_leaves(cache)]


def test_the_latent_pool_is_one_leaf_a_layer_in_the_stated_dtype(programs):
    module = programs[0]
    cfg = module.config
    for dtype in (jnp.float32, jnp.bfloat16):
        cache = make_slot_cache(build(decode_cache_len=64, dtype=dtype), 3)
        pools = latent_leaves(cache)
        assert len(pools) == cfg.num_hidden_layers                 # once a layer, no value pool
        for pool in pools:
            assert pool.shape == (3, 1, cfg.kv_lora_rank + cfg.qk_rope_head_dim, 64)
            assert pool.dtype == dtype
        names = {getattr(p[-1], "key", "") for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]}
        assert not names & {"cached_key", "cached_value"}
        assert slot_capacity(cache) == 64


def test_an_int8_latent_pool_is_refused_by_name(programs):
    with pytest.raises(NotImplementedError, match="cached_latent"):
        make_slot_cache(programs[0], 2, kv_quant=True)


@pytest.mark.parametrize("program", ["prefill", "decode", "join"])
def test_other_slots_latent_rows_are_bit_identical_after_a_tick(programs, program):
    """A tick of slot 0 (a chunk, a token, or a new tenant joining at
    position 0) leaves slot 1's rows, live or parked, as they were; a parked
    slot writes nothing."""
    module, params, prefill, decode = programs
    cache = make_slot_cache(module, 2)
    ids = ids_of(2, 16)
    cache, _ = prefill(params, cache, _int(0, 0), ids, _int(15, 15))      # both slots hold rows
    before = _rows(cache, 1)
    assert all(np.abs(leaf).max() > 0 for leaf in before)
    parked = 64                                                           # slot 1 leaves: parked
    if program == "prefill":
        after, _ = prefill(params, cache, _int(16, parked), ids, _int(9, 15))
    elif program == "decode":
        after, _ = decode(params, cache, _int(16, parked), tokens=_int(5, 9))
    else:
        after, _ = prefill(params, cache, _int(0, parked), ids_of(2, 16, seed=5), _int(15, 15))
    for was, now in zip(before, _rows(after, 1)):
        assert was.tobytes() == now.tobytes()
    assert any(a.tobytes() != b.tobytes() for a, b in zip(_rows(cache, 0), _rows(after, 0)))


@pytest.mark.parametrize("rem", [1, 7, 16])
def test_padding_past_a_slots_real_tokens_changes_no_token(programs, rem):
    """A chunk right-padded past ``rem`` real tokens samples the token that
    ``rem`` tokens alone give, whatever the padding holds."""
    module, params, prefill, _ = programs
    ids = ids_of(1, 16, seed=4)
    noisy = ids.copy()
    noisy[:, rem:] = 201
    _, tok_a = prefill(params, make_slot_cache(module, 1), _int(0), ids, _int(rem - 1))
    _, tok_b = prefill(params, make_slot_cache(module, 1), _int(0), noisy, _int(rem - 1))
    alone = module.apply({"params": params}, ids[:, :rem])
    assert int(tok_a[0]) == int(tok_b[0]) == int(jnp.argmax(alone[0, -1]))


# ---------------------------------------------------------------------------
# latent rows in prefix blocks and in a migrated slot
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine(whole):
    module, params = whole
    return deepspeed_tpu.init_inference(build(decode_cache_len=64), params=params,
                                        dtype=jnp.float32, max_out_tokens=64,
                                        topology=one_device())


def _scheduler(engine, prefix_cache="off"):
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, page_size=8, kv_quant=False, prefill_chunk=16, prefill_interleave=2,
        prefix_cache=prefix_cache))


def _emitted(sched, prompts, n=5):
    reqs = [Request(prompt=p, max_new_tokens=n) for p in prompts]
    for r in reqs:
        sched.submit(r)
        sched.run_until_drained()
    return reqs


def test_a_shared_prefix_restores_latent_rows(engine):
    """With the prefix cache on, a second request with the first's 24-token
    prefix skips those positions' prefill and emits what it emits without
    the cache: the prefix's blocks carried the latent pool's rows."""
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, 256, (24,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(0, 256, (n,)).astype(np.int32)])
               for n in (9, 13)]
    plain = _emitted(_scheduler(engine), prompts)
    shared = _emitted(_scheduler(engine, "on"), prompts)
    assert shared[1].cached_prefix_tokens == 24 and shared[0].cached_prefix_tokens == 0
    assert [r.output for r in shared] == [r.output for r in plain]


def test_a_migrated_slot_carries_its_latent_rows(engine):
    """A request exported mid-decode and admitted by a second scheduler goes
    on as if it had stayed; the bundle's leaves are the latent pools' rows."""
    prompt = ids_of(1, 21, seed=9)[0]
    stay = _emitted(_scheduler(engine), [prompt], n=8)[0]
    src, dst = _scheduler(engine), _scheduler(engine)
    req = Request(prompt=prompt, max_new_tokens=8)
    src.submit(req)
    while len(req.output) < 3:
        src.step()
        src.settle()        # read each program in its own step: stop at three tokens exactly
    bundle, = src.export_inflight()
    leaves = bundle["kv"]["target"]
    assert leaves and all("cached_latent" in key for key in leaves)
    assert {rows.shape for rows in leaves.values()} == {(21 + 2, 1, 40)}
    moved = dst.admit_migrated(bundle)
    dst.run_until_drained()
    assert moved.output == stay.output
