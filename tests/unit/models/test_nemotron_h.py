"""Nemotron-H at the tiny preset on the CPU: the package against the plain
reference (``benchmarks/reference/nemotron_h.py``) on seeded weights, the
chunked scan against the recurrence it equals, the chip's share against the
uncut layer, the rules that move a slot's recurrent state, the sigmoid gate,
and the scheduler's refusals of what a recurrent slot cannot do yet."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import nemotron_h as family
from benchmarks.reference import nemotron_h as ref
from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler, Request, ServingConfig
from deepspeed_tpu.inference.serving.programs import (build_decode_step, build_prefill_step,
                                                      make_apply_fn, make_slot_cache,
                                                      with_write_positions)
from deepspeed_tpu.inference.serving.scheduler import MigrationError
from deepspeed_tpu.models.common import init_cache
from deepspeed_tpu.models.nemotron_h import (NemotronHBlock, NemotronHForCausalLM,
                                             get_nemotron_h_config, ssd_chunk_scan, ssm_step)
from deepspeed_tpu.moe.sharded_moe import _row_rungs, topkrouting
from deepspeed_tpu.utils import trace

EXPERTS, QUARTER = 16, 4


def one_device():
    """The layer that holds a share of its experts is one device's (the
    benchmark's runner builds its engine so)."""
    from deepspeed_tpu.parallel.topology import MeshTopology
    return MeshTopology(devices=jax.devices()[:1])


def build(held=None, **overrides):
    cfg = get_nemotron_h_config("nemotron-h-test", experts_held=held, **overrides)
    return NemotronHForCausalLM(cfg)


def sizes_of(cfg, first=0):
    return ref.Sizes(pattern=cfg.hybrid_override_pattern, n_head=cfg.num_attention_heads,
                     n_kv_head=cfg.num_key_value_heads, mamba_head_dim=cfg.mamba_head_dim,
                     n_groups=cfg.n_groups, top_k=cfg.num_experts_per_tok,
                     routed_scale=cfg.routed_scaling_factor, experts_first=first)


@pytest.fixture(scope="module")
def whole():
    """The uncut model and its seeded weights (float32)."""
    module = build()
    params = nn.meta.unbox(jax.jit(module.init)(jax.random.PRNGKey(30),
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


# ---------------------------------------------------------------------------
# the package against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("held", [None, (0, 4), (4, 4), (12, 4)], ids=str)
def test_full_forward_matches_the_reference(whole, held):
    module, params = whole
    first, count = held or (0, EXPERTS)
    mine = held_params(params, first, count)
    ids = ids_of(2, 21)
    # both sides jitted: eagerly each is dispatched an operation at a time
    got = jax.jit(build(held).apply)({"params": mine}, ids)
    sizes = sizes_of(module.config, first)
    want = jax.jit(lambda flat: ref.forward(flat, ids, sizes))(family.to_reference(mine))
    assert got.shape == (2, 21, 256)
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
    """Six requests over four slots through chunked prefill (8-token chunks
    that end ragged) and decode, a quarter of the experts held."""
    held = (4, 4)
    module = build(held, decode_cache_len=64)
    whole_params = nn.meta.unbox(jax.jit(build().init)(jax.random.PRNGKey(30),
                                                       jnp.zeros((1, 8), jnp.int32))["params"])
    params = held_params(whole_params, *held)
    engine = deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                          max_out_tokens=64, topology=one_device())
    before = dict(trace.recorder().counters)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=4, page_size=8, kv_quant=False, prefill_chunk=8, prefill_interleave=2,
        prefix_cache="off"))
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, 256, (n,)).astype(np.int32), max_new_tokens=6)
            for n in (19, 13, 8, 27, 11, 5)]
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
    r = served[4][which]
    assert len(r.output) == 6
    logits = served_reference_logits[which]
    gap = logits.max(axis=-1) - logits[np.arange(6), np.asarray(r.output)]
    assert gap.max() < 1e-4


@pytest.mark.parametrize("slot", range(4))
def test_a_slots_carried_state_is_the_references_final_state(served, slot):
    """After the last tick, what a slot carries is the state the reference's
    recurrence ends in over its last tenant's prompt and fed-back tokens
    (the last token emitted is never fed)."""
    module, params, held, sched, reqs, _ = served
    carried = [np.asarray(leaf[slot]) for path, leaf
               in jax.tree_util.tree_flatten_with_path(sched._cache)[0]
               if getattr(path[-1], "key", None) == "ssm_state"]
    flat, sizes = family.to_reference(params), sizes_of(module.config, held[0])

    def distance(r):
        ids = np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])[None]
        want = family.reference_final_states(flat, ids, sizes)
        return max(float(np.abs(got.reshape(w[0].shape) - np.asarray(w[0])).max())
                   for got, w in zip(carried, want, strict=True))

    assert min(distance(r) for r in reqs) < 1e-4


def test_serving_counts_state_and_rows(served):
    module, _, _, sched, reqs, counted = served
    fed = sum(len(r.prompt) for r in reqs)
    assert counted["ssm_positions_fed"] == counted["prefill_positions_fed"] == fed
    # the scan runs over what the tick's program ran: the rung's slots x the
    # chunk (ISSUE 33), under the cell's whole shape where a smaller rung ran
    assert counted["ssm_positions_computed"] == counted["prefill_positions_run"]
    assert counted["prefill_positions_run"] <= counted["prefill_positions_computed"] == (
        sched.ticks["prefill"] * sched.slots * 8)
    assert counted["ssm_state_resets"] == len(reqs)            # one join a request
    slots_run = sched.ticks["decode"] * sched.slots + counted["prefill_positions_run"] // 8
    state = 2 * (8 * 16 * 16 * 4 + 3 * 192 * 4)                # two Mamba layers, float32 tail
    assert counted["ssm_state_bytes_touched"] == slots_run * 2 * state
    # every real token takes k experts a layer, here or elsewhere; parked
    # slots and padding route nowhere
    tokens = fed + sum(len(r.output) - 1 for r in reqs)
    assert counted["moe_rows_routed"] + counted["moe_rows_elsewhere"] == tokens * 4 * 2
    assert 0 < counted["moe_rows_routed"] <= counted["moe_rows_computed"]


# ---------------------------------------------------------------------------
# the chunked scan against the recurrence
# ---------------------------------------------------------------------------
def _scan_inputs(length, seed=0, b=2, g=2, r=4, p=16, n=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (b, length, g, r, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, length, g, r)) - 2.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (g, r), minval=0.0, maxval=2.7))
    bm = jax.random.normal(keys[3], (b, length, g, n))
    cm = jax.random.normal(keys[4], (b, length, g, n))
    state = jax.random.normal(keys[5], (b, g, r, p, n))        # not zero
    return x, dt, a, bm, cm, state


def _sequential(x, dt, a, bm, cm, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("length", [5, 8, 21, 40])
def test_chunked_scan_is_the_sequential_recurrence(length):
    inputs = _scan_inputs(length)
    y, state = ssd_chunk_scan(*inputs, chunk=8)
    want_y, want_state = _sequential(*inputs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), rtol=1e-4, atol=1e-4)


def test_a_zero_step_leaves_the_state_alone():
    x, dt, a, bm, cm, state = _scan_inputs(16)
    dt = dt.at[:, 11:].set(0.0)                                # padding past 11 real tokens
    _, padded = ssd_chunk_scan(x, dt, a, bm, cm, state, chunk=8)
    _, real = ssd_chunk_scan(x[:, :11], dt[:, :11], a, bm[:, :11], cm[:, :11], state, chunk=8)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(real), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the share of a four-chip deployment
# ---------------------------------------------------------------------------
def test_four_quarters_of_the_experts_add_up_to_the_uncut_layer(whole):
    """The routed parts of the four shares, with the shared expert (every
    chip's) counted once, are the uncut reference's layer; the package's
    layer with a share held gives that share's part."""
    module, params = whole
    cfg = module.config
    flat = family.to_reference(params)
    bp = ref.block_params(flat, 1)                              # the first E layer
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.hidden_size))
    uncut = ref.experts(bp, x, sizes_of(cfg))
    h = ref.rms_norm(x, bp["ln"])
    weights = ref.router(bp, h, sizes_of(cfg))
    parts = []
    for q in range(EXPERTS // QUARTER):
        first = q * QUARTER
        mine = dict(bp, w1=bp["w1"][first:first + QUARTER], w2=bp["w2"][first:first + QUARTER])
        parts.append(ref.routed(mine, h, weights, sizes_of(cfg, first)))
        # the package's layer holding this quarter: residual + routed part + shared expert
        layer = NemotronHBlock(build((first, QUARTER)).config, "E")
        got = layer.apply({"params": held_params(params, first, QUARTER)["layers_1"]}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x + parts[-1] + ref.shared(bp, h)),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(x + sum(parts) + ref.shared(bp, h)), np.asarray(uncut),
                               atol=2e-5)


# 32 x 128 positions x top-4 = 16,384 copies: the held layer's row buffer is
# 1,024, 4,096 or all 16,384 rows (``sharded_moe._row_rungs``)
RUNG_TOKENS, RUNGS = (32, 128), (1024, 4096, 16384)


@pytest.fixture(scope="module")
def rung_layer(whole):
    """The first expert layer over 4,096 positions, its experts' matrices
    sixteen times the seeded draw (as drawn the routed part is 1e-7 of the
    residual it is added to, and a lost row would go unseen): which of each
    token's experts a share holds, and for a share the routed part by the
    package's layer (jitted once, ``used`` its operand) beside
    the reference's uncompacted sum, every token through every held expert."""
    module, params = whole
    cfg = module.config
    assert _row_rungs(RUNG_TOKENS[0] * RUNG_TOKENS[1] * cfg.num_experts_per_tok) == RUNGS
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * 16 if "deepspeed_experts" in [getattr(p, "key", "") for p in path]
        else leaf, params)
    bp = ref.block_params(family.to_reference(params), 1)
    x = jax.random.normal(jax.random.PRNGKey(36), RUNG_TOKENS + (cfg.hidden_size,))
    h = ref.rms_norm(x, bp["ln"])
    weights = ref.router(bp, h, sizes_of(cfg))
    rest = x + ref.shared(bp, h)
    with jax.default_matmul_precision("highest"):
        scores = np.sort(np.asarray(jax.nn.sigmoid(h @ bp["router"]) + bp["router_bias"]), axis=-1)
    # no token's fourth and fifth scores so close that another rounding picks the other
    assert (scores[..., -4] - scores[..., -5]).min() > 5e-6
    made = {}

    def share(first, count):
        if (first, count) not in made:
            layer = NemotronHBlock(build((first, count)).config, "E")
            mine = held_params(params, first, count)["layers_1"]
            bank = dict(bp, w1=bp["w1"][first:first + count], w2=bp["w2"][first:first + count])

            @jax.jit
            def routed(used):
                out, state = layer.apply({"params": mine}, x, used=used, mutable=["cache"])
                want = ref.routed(bank, h, weights * used.reshape(RUNG_TOKENS + (1,)),
                                  sizes_of(cfg, first))
                return out - rest, want, state["cache"]["mixer"]["moe_rows"]
            made[first, count] = routed
        held = np.asarray(weights).reshape(-1, EXPERTS)[:, first:first + count] > 0
        return held.sum(axis=1), held, made[first, count]

    return share


def tokens_that_hold(per_token, rows):
    """A ``used_token`` mask whose tokens' held copies add up to ``rows``."""
    used, left = np.zeros(len(per_token), bool), rows
    for t in np.argsort(-per_token, kind="stable"):         # the fullest first, ones to finish
        if 0 < per_token[t] <= left:
            used[t], left = True, left - per_token[t]
    assert left == 0
    if rows == 0:
        used[per_token == 0] = True                         # real tokens, every copy elsewhere
    return used


@pytest.mark.parametrize("held, rows, rung", [
    ((0, 8), 0, 0), ((0, 8), 1, 0), ((0, 8), 1023, 0), ((0, 8), 1024, 0), ((0, 8), 1025, 1),
    ((0, 8), 4095, 1), ((0, 8), 4096, 1), ((0, 8), 4097, 2), ((4, 4), 700, 0),
    ((0, EXPERTS), 16384, 2),                               # every copy held and used
    ((0, 8), None, 0),                                      # every token unused
], ids=str)
def test_the_row_buffer_follows_the_rows_held_and_loses_none(rung_layer, held, rows, rung):
    """On every rung and on both sides of each boundary the layer with a
    share held gives the uncompacted sum over that share, and the fifth
    counter names the buffer it took."""
    per_token, held_by, routed = rung_layer(*held)
    used = (np.zeros(len(per_token), bool) if rows is None
            else tokens_that_hold(per_token, rows))
    got, want, counted = routed(jnp.asarray(used))
    assert rows in (0, None) or float(jnp.abs(want).max()) > 1e-3      # not a sum of nothing
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    here, visited, anywhere, touched, buffered = np.asarray(counted)
    assert here == (rows or 0) and anywhere == 4 * used.sum()
    assert buffered == RUNGS[rung] and here <= visited
    assert touched == held_by[used].any(axis=0).sum()


def test_four_vocabulary_slices_concatenate_to_the_whole(whole):
    module, params = whole
    flat = family.to_reference(params)
    ids = ids_of(2, 7)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, module.config.hidden_size))
    slices = [(q * 64, 64) for q in range(4)]
    logits = jnp.concatenate([ref.head(flat, x, s) for s in slices], axis=-1)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref.head(flat, x)), atol=1e-5)
    embedded = sum(ref.embed(flat, ids, s) for s in slices)     # each id in one slice
    np.testing.assert_allclose(np.asarray(embedded), np.asarray(ref.embed(flat, ids)), atol=0)


# ---------------------------------------------------------------------------
# what moves a slot's recurrent state
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def programs(whole):
    module, params = whole
    module = build(decode_cache_len=64)
    apply_fn = make_apply_fn(module)
    prefill = jax.jit(build_prefill_step(apply_fn, False, 1.0, 0, 1.0))
    decode = jax.jit(build_decode_step(apply_fn, False, 1.0, 0, 1.0))
    return module, params, prefill, decode


def _state(cache, slot):
    """Every recurrent leaf of one slot, as host arrays."""
    return [np.asarray(leaf[slot]) for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if getattr(path[-1], "key", "") in ("ssm_state", "conv_state")]


def _int(*values):
    return np.asarray(values, np.int32)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_parked_slots_state_is_bit_identical_after_a_tick(programs, program):
    module, params, prefill, decode = programs
    cache = make_slot_cache(module, 2)
    ids = ids_of(2, 8)
    cache, _ = prefill(params, cache, _int(0, 0), ids, _int(7, 7))        # both slots hold state
    before = _state(cache, 1)
    assert any(np.abs(leaf).max() > 0 for leaf in before)
    parked = 64                                                           # the sentinel position
    if program == "prefill":
        after, _ = prefill(params, cache, _int(8, parked), ids, _int(7, 7))
    else:
        after, _ = decode(params, cache, _int(8, parked), tokens=_int(5, 9))
    for was, now in zip(before, _state(after, 1)):
        assert was.tobytes() == now.tobytes()
    assert any(a.tobytes() != b.tobytes() for a, b in zip(_state(cache, 0), _state(after, 0)))


def test_a_join_at_position_zero_forgets_the_previous_tenant(programs):
    module, params, prefill, _ = programs
    ids, other = ids_of(1, 8, seed=1), ids_of(1, 8, seed=2)
    fresh, tok_fresh = prefill(params, make_slot_cache(module, 1), _int(0), ids, _int(7))
    used, _ = prefill(params, make_slot_cache(module, 1), _int(0), other, _int(7))
    used, _ = prefill(params, used, _int(8), other, _int(7))               # a long-gone tenant
    joined, tok_joined = prefill(params, used, _int(0), ids, _int(7))
    for a, b in zip(_state(fresh, 0), _state(joined, 0)):
        assert a.tobytes() == b.tobytes()
    assert int(tok_fresh[0]) == int(tok_joined[0])


@pytest.mark.parametrize("rem", [1, 3, 5, 8])
def test_padding_past_a_slots_real_tokens_changes_nothing(programs, rem):
    """A chunk right-padded past ``rem`` real tokens leaves the state and
    the tail where ``rem`` tokens alone leave them, whatever the padding."""
    module, params, prefill, _ = programs
    ids = ids_of(1, 8, seed=4)
    padded = ids.copy()
    padded[:, rem:] = 0
    noisy = ids.copy()
    noisy[:, rem:] = 201
    a, tok_a = prefill(params, make_slot_cache(module, 1), _int(0), padded, _int(rem - 1))
    b, tok_b = prefill(params, make_slot_cache(module, 1), _int(0), noisy, _int(rem - 1))
    for x, y in zip(_state(a, 0), _state(b, 0)):
        assert x.tobytes() == y.tobytes()
    assert int(tok_a[0]) == int(tok_b[0])
    # and they are what the recurrence over the real tokens alone gives
    cache = init_cache(module, 1)
    _, upd = module.apply({"params": params, "cache": cache}, ids[:, :rem], decode=True,
                          mutable=["cache"])
    for x, y in zip(_state(a, 0), _state(upd["cache"], 0)):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_length_leaves_say_how_many_tokens_are_real(programs):
    module = programs[0]
    cache = with_write_positions(make_slot_cache(module, 3), jnp.asarray(_int(0, 64, 17)),
                                 jnp.asarray(_int(8, 8, 3)))
    assert list(np.asarray(cache["chunk_length"])) == [8, 0, 3]            # the parked slot: none
    assert list(np.asarray(cache["position_index"])) == [0, 64, 17]
    decode = with_write_positions(make_slot_cache(module, 3), jnp.asarray(_int(5, 64, 9)))
    assert list(np.asarray(decode["chunk_length"])) == [1, 0, 1]


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------
def test_sigmoid_gate_chooses_by_score_plus_bias_and_weighs_by_score():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0, 0.5]])
    bias = jnp.asarray([-5.0, 0.0, 0.0, 3.0, 0.0, 0.0])
    _, routing, _ = topkrouting(logits, 2, 1.0, 1, drop_tokens=False, normalize=True,
                                score="sigmoid", select_bias=bias, scale=2.5)
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    # the bias lifts expert 3 over expert 0, which has the largest score
    assert sorted(np.asarray(routing.expert)[0].tolist()) == [1, 3]
    order = np.asarray(routing.expert)[0]
    want = s[order] / s[order].sum() * 2.5                                 # the bias is not in them
    np.testing.assert_allclose(np.asarray(routing.weight)[0], want, rtol=1e-6)
    _, plain, _ = topkrouting(logits, 2, 1.0, 1, drop_tokens=False, normalize=False,
                              score="sigmoid")
    assert np.asarray(plain.expert)[0].tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(plain.weight)[0], s[[0, 1]], rtol=1e-6)


# ---------------------------------------------------------------------------
# refusals, each by the mechanism's name
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine(whole):
    module, params = whole
    return deepspeed_tpu.init_inference(build(decode_cache_len=64), params=params,
                                        dtype=jnp.float32, max_out_tokens=64,
                                        topology=one_device())


def _config(**knobs):
    return ServingConfig(**{**dict(slots=2, page_size=8, kv_quant=False, prefill_chunk=8,
                                   prefix_cache="off"), **knobs})


def test_prefix_cache_over_recurrent_state_is_refused_by_name(engine):
    with pytest.raises(NotImplementedError, match="prefix_cache.*recurrent state.*snapshot"):
        ContinuousBatchingScheduler(engine, _config(prefix_cache="on"))


def test_a_drafter_over_recurrent_state_is_refused_by_name(engine, whole):
    module, params = whole
    with pytest.raises(NotImplementedError, match="speculative decoding.*recurrent state"):
        ContinuousBatchingScheduler(engine, _config(), drafter=(module, params))


@pytest.mark.parametrize("call", ["export_inflight", "admit_migrated"])
def test_migration_of_a_recurrent_slot_is_refused_by_name(engine, call):
    sched = ContinuousBatchingScheduler(engine, _config())
    with pytest.raises(MigrationError, match="live migration.*recurrent state.*snapshot"):
        if call == "export_inflight":
            sched.export_inflight()
        else:
            sched.admit_migrated({"state": "active"})
