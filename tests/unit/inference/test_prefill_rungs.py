"""A prefill tick runs a program the size of the slots it feeds (ISSUE 33):
the prefill program exists at a short ladder of sequence counts ("rungs"),
a tick runs the smallest that holds its fed slots, and a rung below the
whole one is handed the slots it runs. On the CPU, at the test presets of the
three families whose caches have a ladder: the same tokens as the whole
program emits, no row of any other slot touched, the smallest rung that
fits, and the whole rung the program it was before. The fourth, a latent
pool, has the whole rung alone."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

import deepspeed_tpu
from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler, Request,
                                             ServingConfig, make_slot_cache, slot_capacity)
from deepspeed_tpu.inference.serving.programs import (INDEX_LEAVES, LENGTH_LEAVES, POOL_LEAVES,
                                                      STATE_LEAVES, _leaf_name,
                                                      build_prefill_step, make_apply_fn,
                                                      prefill_rungs, with_counters,
                                                      with_next_tokens, with_write_positions,
                                                      without_next_tokens)
from deepspeed_tpu.models.common import COUNTER_LEAVES
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils import trace

SLOTS, CHUNK = 8, 8
FAMILIES = ["gpt2-test", "olmoe-test", "nemotron-h-test"]


def _module(family):
    """The family's model at its test preset, 64 positions a slot; the two
    that hold a share of their experts hold a quarter."""
    if family == "gpt2-test":
        from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
        return GPT2LMHeadModel(get_gpt2_config("test", n_layer=2, n_positions=64))
    if family == "olmoe-test":
        from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config
        return LlamaForCausalLM(get_llama_config("olmoe-test", decode_cache_len=64))
    if family == "nemotron-h-test":
        from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM, get_nemotron_h_config
        return NemotronHForCausalLM(get_nemotron_h_config(
            "nemotron-h-test", experts_held=(4, 4), decode_cache_len=64))
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM, get_deepseek_v3_config
    return DeepseekV3ForCausalLM(get_deepseek_v3_config(
        "deepseek-v3-test", experts_held=(4, 4), decode_cache_len=64))


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


@pytest.fixture(scope="module", params=FAMILIES)
def engine(request):
    set_topology(None)
    module = _module(request.param)
    params = nn.meta.unbox(module.init(jax.random.PRNGKey(33),
                                       jnp.zeros((1, 8), jnp.int32))["params"])
    engine = deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                          max_out_tokens=64,
                                          topology=MeshTopology(devices=jax.devices()[:1]))
    yield engine
    set_topology(None)


def _scheduler(engine, chunk=CHUNK, **sampling):
    # an int8 pool where the family has one to quantise (a latent pool has not)
    kv_quant = "Deepseek" not in type(engine.module).__name__
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, page_size=8, kv_quant=kv_quant, prefill_chunk=chunk, prefill_interleave=2,
        prefix_cache="off", **sampling))


def _serve(sched):
    """Mixed joins and leaves: prompts of one chunk and of several, that end
    ragged, arriving alone and six at once, outputs that end at different
    ticks. Returns the requests and the rung of every prefill tick."""
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=rng.integers(0, 256, (p,)).astype(np.int32), max_new_tokens=n)
            for p, n in zip([5, 21, 9, 30, 3, 17, 40, 12, 8, 19, 26, 7],
                            [7, 3, 9, 4, 8, 5, 6, 7, 3, 9, 2, 5])]
    arrivals = {0: [0], 1: [1, 2], 6: [3], 11: [4, 5, 6, 7, 8, 9], 13: [10, 11]}
    counters, rungs, tick = trace.recorder().counters, [], 0
    while any(not r.done for r in reqs):
        for i in arrivals.get(tick, []):
            sched.submit(reqs[i])
        before = counters.get("prefill_positions_run", 0)
        sched.step()        # returns the kind it read back; the counter says what it dispatched
        if counters.get("prefill_positions_run", 0) > before:
            rungs.append((counters["prefill_positions_run"] - before) // CHUNK)
        tick += 1
        assert tick < 500
    return reqs, rungs


# ---------------------------------------------------------------------------
# (a) the same tokens as when every tick is forced onto the whole rung
# ---------------------------------------------------------------------------
def test_a_run_emits_what_the_whole_rung_emits(engine):
    sched = _scheduler(engine)
    assert sched._rungs == (2, 8)
    reqs, rungs = _serve(sched)
    assert set(rungs) == {2, 8}                          # both rungs ran
    whole = _scheduler(engine)
    whole._rungs = (SLOTS,)
    reqs_whole, rungs_whole = _serve(whole)
    assert set(rungs_whole) == {SLOTS} and len(rungs_whole) == len(rungs)   # the same schedule
    for got, want in zip(reqs, reqs_whole):
        assert len(got.output) == got.max_new_tokens
        assert list(got.output) == list(want.output)


def test_a_one_token_chunk_on_a_rung_emits_what_the_whole_rung_emits(engine):
    """A chunk of one token takes the models' one-token forms (the absorbed
    latent attention, the one-step recurrence), which read a slot's rows
    whole: on a rung, the rows of the slots it runs."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, (p,)).astype(np.int32) for p in (3, 5, 2)]
    outputs = []
    for whole in (False, True):
        sched = _scheduler(engine, chunk=1)
        if whole:
            sched._rungs = (SLOTS,)
        reqs = [Request(prompt=p, max_new_tokens=4) for p in prompts]
        sched.submit(reqs[0])
        sched.step()
        for r in reqs[1:]:
            sched.submit(r)
        sched.run_until_drained(max_ticks=200)
        outputs.append([list(r.output) for r in reqs])
    assert outputs[0] == outputs[1] and all(len(o) == 4 for o in outputs[0])


def test_a_sampling_run_hops_between_rungs_on_the_programs_of_warmup(engine):
    """A server that samples (the RLHF rollout's): the tick's key rides
    behind a rung's operands as behind the whole program's, ticks hop between
    the rungs, nothing is compiled after ``warmup``, every request draws its
    tokens from the vocabulary, and the same seed draws them again. The draws
    are NOT those of a whole-rung run: a rung draws over ``[n, vocabulary]``
    logits with the tick's key, the whole program over ``[slots,
    vocabulary]``, so a seeded sampling run repeats on one ladder and not
    across ladders (greedy runs are equal across them, above)."""
    outputs = []
    for _ in range(2):
        sched = _scheduler(engine, do_sample=True, temperature=0.8, top_k=20)
        sched.warmup()
        warm = {name: fn._cache_size() for name, fn in sched.fns.items()}
        assert warm == {"prefill": 1, "prefill_rung": 1, "decode": 1}
        reqs, rungs = _serve(sched)
        assert set(rungs) == {2, 8}
        assert {name: fn._cache_size() for name, fn in sched.fns.items()} == warm
        for r in reqs:
            assert len(r.output) == r.max_new_tokens
            assert all(0 <= int(t) < 256 for t in r.output)
        outputs.append([list(r.output) for r in reqs])
    assert outputs[0] == outputs[1]
    assert len({tuple(o) for o in outputs[0]}) > 1       # draws, not one token over and over


# ---------------------------------------------------------------------------
# (b) a rung tick leaves every other slot's rows, and its padded entries', bit-equal
# ---------------------------------------------------------------------------
def _rows(cache):
    """Every leaf that holds a row a slot, by path, as host copies."""
    by_row = POOL_LEAVES + STATE_LEAVES
    return {jax.tree_util.keystr(path): np.array(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if _leaf_name(path) in by_row or _leaf_name(path).endswith("_scale")}


def test_a_rung_tick_touches_no_other_slot(engine):
    sched = _scheduler(engine)
    rng = np.random.default_rng(11)
    # a cache that is nowhere zero, so a write and no write are told apart
    sched._cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if _leaf_name(path) in INDEX_LEAVES + LENGTH_LEAVES + COUNTER_LEAVES
        else jnp.asarray(rng.integers(1, 5, leaf.shape), leaf.dtype), sched._cache)
    parked = sched.capacity
    before = _rows(sched._cache)
    assert any("ssm_state" in k for k in before) == sched._recurrent
    # slots 6 and 1 are fed (in that order: a row is not its slot's number),
    # 3 and 4 fill the rung, parked; 0, 2, 5 and 7 are not in it
    slot_ids = np.array([6, 1, 3, 4], np.int32)
    write_pos = np.array([10, 0, parked, parked], np.int32)
    ids = rng.integers(1, 256, (4, CHUNK)).astype(np.int32)
    last_idx = np.array([CHUNK - 1, 4, CHUNK - 1, CHUNK - 1], np.int32)
    cache, tok = sched.fns["prefill_rung"](sched._serve_params, sched._cache, slot_ids,
                                           write_pos, ids, last_idx)
    assert np.asarray(tok).shape[0] == 4 + sum(w for _, w in sched._counters)
    after = _rows(cache)
    assert after.keys() == before.keys()
    for key, was in before.items():
        now = after[key]
        assert now.shape == was.shape and now.dtype == was.dtype
        for slot in (0, 2, 5, 7, 3, 4):
            assert np.array_equal(now[slot], was[slot]), (key, slot)
        for slot in (6, 1):
            assert not np.array_equal(now[slot], was[slot]), (key, slot)
    # and the fed rows are what the whole program writes for the same feed
    whole_pos = np.full(SLOTS, parked, np.int32)
    whole_ids, whole_last = np.zeros((SLOTS, CHUNK), np.int32), np.full(SLOTS, CHUNK - 1, np.int32)
    whole_pos[[6, 1]], whole_ids[[6, 1]], whole_last[[6, 1]] = write_pos[:2], ids[:2], last_idx[:2]
    again = _scheduler(engine)
    again._cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(before[jax.tree_util.keystr(path)])
        if jax.tree_util.keystr(path) in before else leaf, again._cache)
    cache_whole, tok_whole = again.fns["prefill"](again._serve_params, again._cache, whole_pos,
                                                  whole_ids, whole_last)
    assert list(np.asarray(tok)[:2]) == list(np.asarray(tok_whole)[[6, 1]])
    for key, want in _rows(cache_whole).items():
        np.testing.assert_allclose(after[key].astype(np.float32), want.astype(np.float32),
                                   rtol=1e-5, atol=1e-5, err_msg=key)


# ---------------------------------------------------------------------------
# (c) the smallest rung that holds the fed slots
# ---------------------------------------------------------------------------
def test_the_ladder_is_a_quarter_and_all():
    assert prefill_rungs(32) == (8, 32)
    assert prefill_rungs(64) == (16, 64)
    assert prefill_rungs(4) == (1, 4)
    assert prefill_rungs(2) == (2,) and prefill_rungs(1) == (1,)     # no quarter to take
    # a mesh shards the slots over ``data``: one rung there, the whole
    assert prefill_rungs(32, mesh_size=4) == (32,)


def test_a_latent_pool_has_the_whole_rung_alone():
    """The fourth family's prefill attention walks the fed slots' pools a
    block at a time whatever the program's size: no smaller program is built
    for it (the long-document cell read its set-up cost and no gain), its
    ticks run every slot, and what lays ``cache_slots`` beside the pools
    refuses a latent pool by name."""
    from deepspeed_tpu.inference.serving.programs import rows_of_slots
    set_topology(None)
    module = _module("joyai-llm-flash-test")
    params = nn.meta.unbox(module.init(jax.random.PRNGKey(33),
                                       jnp.zeros((1, 8), jnp.int32))["params"])
    engine = deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                          max_out_tokens=64,
                                          topology=MeshTopology(devices=jax.devices()[:1]))
    sched = _scheduler(engine)
    assert sched._rungs == (SLOTS,) == prefill_rungs(SLOTS, 1, sched._cache)
    sched.warmup()
    assert sched.fns["prefill_rung"]._cache_size() == 0
    reqs, rungs = _serve(sched)
    assert set(rungs) == {SLOTS} and all(len(r.output) == r.max_new_tokens for r in reqs)
    with pytest.raises(NotImplementedError, match="latent pool"):
        rows_of_slots(sched._cache, jnp.arange(2))


@pytest.mark.parametrize("fed, rung", [(1, 2), (2, 2), (3, 8), (7, 8), (8, 8)])
def test_the_rung_is_the_smallest_that_holds_the_fed_slots(engine, fed, rung):
    sched = _scheduler(engine)
    slots = list(range(SLOTS))[::-1][:fed]               # 7, 6, ...: not the first rows
    rows = sched._rung_rows(slots, sched._rungs)
    assert len(rows) == rung and len(set(rows.tolist())) == rung
    if rung == SLOTS:
        assert rows.tolist() == list(range(SLOTS))       # every slot in its place
    else:
        assert rows[:fed].tolist() == slots              # the fed slots, then others
        assert not set(rows[fed:].tolist()) & set(slots)


def test_prefill_positions_computed_still_counts_ticks(engine):
    """The benchmark counts prefill ticks as ``prefill_positions_computed``
    over slots x chunk (``benchmarks/lib/olmoe_ticks.py``): that counter is
    the cell's shape whatever rung ran; ``prefill_positions_run`` is the
    rung's."""
    counters = trace.recorder().counters
    before = dict(counters)
    sched = _scheduler(engine)
    _, rungs = _serve(sched)
    grew = {k: counters[k] - before.get(k, 0) for k in counters}
    assert grew["prefill_positions_computed"] == SLOTS * CHUNK * len(rungs)
    assert grew["prefill_positions_computed"] == SLOTS * CHUNK * sched.ticks["prefill"]
    assert grew["prefill_positions_run"] == CHUNK * sum(
        n * grew[f"prefill_ticks_rung_{n}"] for n in (2, 8))
    assert grew["prefill_positions_fed"] <= CHUNK * grew["prefill_slots_fed"]
    assert grew["prefill_slots_fed"] <= sum(rungs) < SLOTS * len(rungs)
    assert {n: grew[f"prefill_ticks_rung_{n}"] for n in (2, 8)} == {
        n: rungs.count(n) for n in (2, 8)}
    if sched._recurrent:
        # the state of the slots a tick ran, read and written once
        ticks = sched.ticks["decode"] * SLOTS + sum(rungs)
        assert grew["ssm_state_bytes_touched"] == 2 * sched._state_bytes * ticks
        assert grew["ssm_positions_computed"] == grew["prefill_positions_run"]


# ---------------------------------------------------------------------------
# (d) the whole rung is the program it was
# ---------------------------------------------------------------------------
def _parent_prefill_step(apply_fn):
    """The prefill program as the parent of ISSUE 33 built it (greedy), with
    the token each fed slot is given left in the cache (ISSUE 35)."""

    def prefill(params, cache, write_pos, ids, last_idx):
        cache, held = without_next_tokens(cache)
        logits, cache = apply_fn(params, with_write_positions(cache, write_pos, last_idx + 1), ids)
        last = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        held = jnp.where(write_pos < slot_capacity(cache), tok, held)
        return with_next_tokens(cache, held), with_counters(cache, tok)

    return prefill


def test_the_whole_rung_lowers_as_the_program_before_rungs(engine):
    apply_fn = make_apply_fn(engine.module, engine._mparams)
    cache = make_slot_cache(engine.module, SLOTS)
    operands = (engine.params, cache, np.full(SLOTS, slot_capacity(cache), np.int32),
                np.zeros((SLOTS, CHUNK), np.int32), np.zeros(SLOTS, np.int32))
    texts = [jax.jit(step, donate_argnums=(1,)).lower(*operands).as_text()
             for step in (build_prefill_step(apply_fn, False, 1.0, 0, 1.0),
                          _parent_prefill_step(apply_fn))]
    assert texts[0] == texts[1]
    assert "cache_slots" not in texts[0]
    # and a rung's program is another: it takes the slots it runs
    rung = jax.jit(build_prefill_step(apply_fn, False, 1.0, 0, 1.0, rung=True),
                   donate_argnums=(1,)).lower(
        operands[0], cache, np.arange(2, dtype=np.int32), *(o[:2] for o in operands[2:]))
    assert rung.as_text() != texts[0]
