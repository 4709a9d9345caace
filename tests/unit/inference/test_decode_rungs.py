"""A decode tick runs the rung that holds the slots it feeds (ISSUE 42): the
plain decode program exists at the kind of ladder the prefill program has
(``programs.decode_rungs``: a quarter of the slots where that is at least 8
sequences, and all), and a rung below the whole is handed the slots it runs.
On the CPU, at the test presets of the three families whose caches have a
ladder, 32 slots so that the ladder is ``(8, 32)``: the same tokens as the
whole program emits, no row of any other slot touched, the smallest rung that
fits, the counters, nothing compiled after warm-up, and the whole program the
program it was."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler, Request,
                                             ServingConfig, make_slot_cache, slot_capacity)
from deepspeed_tpu.inference.serving.programs import (INDEX_LEAVES, LENGTH_LEAVES, POOL_LEAVES,
                                                      STATE_LEAVES, TOKEN_LEAF, _leaf_name,
                                                      build_decode_step, decode_rungs,
                                                      make_apply_fn, rows_of_slots,
                                                      with_counters, with_next_tokens,
                                                      with_write_positions, without_next_tokens)
from deepspeed_tpu.models.common import COUNTER_LEAVES
from deepspeed_tpu.parallel.topology import set_topology
from deepspeed_tpu.utils import trace

# the three families' engines (module-scoped, one a family) and the topology reset
from tests.unit.inference.test_prefill_rungs import _clear_topology, _module, engine  # noqa: F401

SLOTS, CHUNK, RUNGS = 32, 8, (8, 32)


def _scheduler(engine, **sampling):
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, page_size=8, kv_quant=True, prefill_chunk=CHUNK, prefill_interleave=2,
        prefix_cache="off", **sampling))


def _serve(sched):
    """Mixed joins and leaves: one request alone, a few, then twelve at once
    whose prompts are one chunk (so twelve decode together: over the quarter
    rung of both programs), the tail as they leave. Returns the requests and
    the counters the run moved."""
    rng = np.random.default_rng(7)
    prompts = [5, 21, 9, 30, 3, 7, 4, 2, 8, 6, 5, 7, 1, 4, 3, 8, 6, 10, 22, 9]
    outputs = [7, 3, 9, 4, 8, 5, 6, 7, 3, 9, 2, 5, 8, 6, 4, 9, 3, 7, 5, 6]
    reqs = [Request(prompt=rng.integers(0, 256, (p,)).astype(np.int32), max_new_tokens=n)
            for p, n in zip(prompts, outputs)]
    arrivals = {0: [0], 1: [1, 2], 6: [3], 11: list(range(4, 16)), 30: [16, 17, 18, 19]}
    counters, tick = trace.recorder().counters, 0
    before = dict(counters)
    while sched.busy or tick <= max(arrivals):
        for i in arrivals.get(tick, []):
            sched.submit(reqs[i])
        sched.step()
        tick += 1
        assert tick < 500
    return reqs, {k: v - before.get(k, 0) for k, v in counters.items()}


@pytest.fixture(scope="module")
def runs(engine):
    """One warmed run on the ladder and one with every decode tick forced
    onto the whole rung, with what each counted and compiled."""
    set_topology(None)
    sched = _scheduler(engine)
    sched.warmup()
    warm = {name: fn._cache_size() for name, fn in sched.fns.items()}
    reqs, counted = _serve(sched)
    after = {name: fn._cache_size() for name, fn in sched.fns.items()}
    whole = _scheduler(engine)
    whole._decode_rungs = (SLOTS,)
    reqs_whole, counted_whole = _serve(whole)
    return {"sched": sched, "warm": warm, "after": after, "reqs": reqs, "counted": counted,
            "whole": whole, "reqs_whole": reqs_whole, "counted_whole": counted_whole}


# ---------------------------------------------------------------------------
# (a) the same tokens as when every decode tick is forced onto the whole rung
# ---------------------------------------------------------------------------
def test_a_run_emits_what_the_whole_rung_emits(runs):
    assert runs["sched"]._decode_rungs == RUNGS == runs["sched"]._rungs
    counted, counted_whole = runs["counted"], runs["counted_whole"]
    assert all(counted[f"decode_ticks_rung_{n}"] > 0 for n in RUNGS)        # both rungs ran
    assert counted_whole.get("decode_ticks_rung_8", 0) == 0
    # the same schedule: which slots decode in which tick does not change
    assert counted_whole["decode_ticks_rung_32"] == sum(
        counted[f"decode_ticks_rung_{n}"] for n in RUNGS) == runs["sched"].ticks["decode"]
    assert counted_whole["decode_slots_fed"] == counted["decode_slots_fed"]
    for got, want in zip(runs["reqs"], runs["reqs_whole"]):
        assert len(got.output) == got.max_new_tokens
        assert list(got.output) == list(want.output)


# ---------------------------------------------------------------------------
# (b) a rung tick leaves every other slot's rows, and its parked entries', bit-equal
# ---------------------------------------------------------------------------
def _rows(cache):
    """Every leaf that holds a row a slot, by path, as host copies."""
    by_row = POOL_LEAVES + STATE_LEAVES + INDEX_LEAVES + LENGTH_LEAVES + (TOKEN_LEAF,)
    return {jax.tree_util.keystr(path): np.array(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if _leaf_name(path) in by_row or _leaf_name(path).endswith("_scale")}


def test_a_rung_tick_touches_no_other_slot(engine):
    sched = _scheduler(engine)
    rng = np.random.default_rng(11)
    # a cache that is nowhere zero, so a write and no write are told apart
    sched._cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if _leaf_name(path) in COUNTER_LEAVES
        else jnp.asarray(rng.integers(1, 5, leaf.shape), leaf.dtype), sched._cache)
    parked = sched.capacity
    before = _rows(sched._cache)
    assert any("ssm_state" in k for k in before) == sched._recurrent
    assert any(k.endswith("_scale']") for k in before)
    # slots 30 and 1 are fed (in that order: a row is not its slot's number),
    # six more fill the rung, parked; the other 24 are not in it
    slot_ids = np.array([30, 1, 3, 4, 17, 9, 22, 31], np.int32)
    write_pos = np.array([10, 63] + [parked] * 6, np.int32)
    fed, filled = [30, 1], [3, 4, 17, 9, 22, 31]
    cache, tok = sched.fns["decode_rung"](sched._serve_params, sched._cache, slot_ids, write_pos)
    tok = np.asarray(tok)
    assert tok.shape[0] == 8 + sum(w for _, w in sched._counters)
    after = _rows(cache)
    assert after.keys() == before.keys()
    for key, was in before.items():
        now = after[key]
        assert now.shape == was.shape and now.dtype == was.dtype
        carried = key.split("'")[-2] in INDEX_LEAVES + LENGTH_LEAVES
        for slot in range(SLOTS):
            if slot in fed or (carried and slot in filled):
                continue        # an index leaf's value is dead: the next operand sets it
            assert np.array_equal(now[slot], was[slot]), (key, slot)
        if not carried:
            for slot in fed:
                assert not np.array_equal(now[slot], was[slot]), (key, slot)
    # each fed slot's sampled token is left for its next tick, of either size
    assert after[f"['{TOKEN_LEAF}']"][fed].tolist() == tok[:2].tolist()
    # and the fed rows are what the whole program writes for the same feed
    whole_pos = np.full(SLOTS, parked, np.int32)
    whole_pos[fed] = write_pos[:2]
    again = _scheduler(engine)
    again._cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(before[jax.tree_util.keystr(path)])
        if jax.tree_util.keystr(path) in before else leaf, again._cache)
    cache_whole, tok_whole = again.fns["decode"](again._serve_params, again._cache, whole_pos)
    assert tok[:2].tolist() == np.asarray(tok_whole)[fed].tolist()
    for key, want in _rows(cache_whole).items():
        if key.split("'")[-2] not in INDEX_LEAVES + LENGTH_LEAVES:
            np.testing.assert_allclose(after[key].astype(np.float32), want.astype(np.float32),
                                       rtol=1e-5, atol=1e-5, err_msg=key)


# ---------------------------------------------------------------------------
# (c) the ladder, and the smallest rung that holds the fed slots
# ---------------------------------------------------------------------------
def test_the_ladder_is_a_quarter_of_at_least_eight_and_all():
    assert decode_rungs(32) == (8, 32)
    assert decode_rungs(64) == (16, 64)
    # under 32 slots a quarter is under a tile of rows: the whole program alone
    assert [decode_rungs(n) for n in (16, 8, 4, 1)] == [(16,), (8,), (4,), (1,)]
    # a mesh shards the slots over ``data``: one rung there, the whole
    assert decode_rungs(32, mesh_size=4) == (32,)


def test_a_latent_pool_has_the_whole_rung_alone():
    """A latent pool is read in place, a slot at a time: the program over it
    has one size, and what lays ``cache_slots`` beside the pools refuses it
    by name."""
    cache = jax.eval_shape(lambda: make_slot_cache(_module("joyai-llm-flash-test"), SLOTS))
    assert decode_rungs(SLOTS, 1, cache) == (SLOTS,)
    with pytest.raises(NotImplementedError, match="latent pool"):
        rows_of_slots(cache, jnp.arange(8))


@pytest.mark.parametrize("fed, rung", [(1, 8), (5, 8), (8, 8), (9, 32), (32, 32)])
def test_the_rung_is_the_smallest_that_holds_the_fed_slots(engine, fed, rung):
    sched = _scheduler(engine)
    slots = list(range(SLOTS))[::-1][:fed]               # 31, 30, ...: not the first rows
    rows = sched._rung_rows(slots, sched._decode_rungs)
    assert len(rows) == rung and len(set(rows.tolist())) == rung
    if rung == SLOTS:
        assert rows.tolist() == list(range(SLOTS))       # every slot in its place
    else:
        assert rows[:fed].tolist() == slots              # the fed slots, then others
        assert not set(rows[fed:].tolist()) & set(slots)


# ---------------------------------------------------------------------------
# (d) the counters: ``_computed`` the cell's shape, ``_run`` what ran
# ---------------------------------------------------------------------------
def test_decode_slots_computed_still_counts_ticks(runs):
    """The benchmark counts decode ticks as ``decode_slots_computed`` over
    the slots (``benchmarks/lib/olmoe_ticks.py``, ``nemotron_h_ticks.py``):
    that counter is the cell's shape whatever rung ran; ``decode_slots_run``
    is the rung's."""
    sched, grew = runs["sched"], runs["counted"]
    ticks = {n: grew[f"decode_ticks_rung_{n}"] for n in RUNGS}
    assert grew["decode_slots_computed"] == SLOTS * sched.ticks["decode"]
    assert grew["decode_slots_run"] == sum(n * t for n, t in ticks.items())
    assert grew["decode_slots_fed"] <= grew["decode_slots_run"] < grew["decode_slots_computed"]
    assert grew["decode_slots_fed"] == sum(len(r.output) - 1 for r in runs["reqs"])
    if sched._recurrent:
        # the state of the slots a tick ran, read and written once
        ran = grew["decode_slots_run"] + grew["prefill_positions_run"] // CHUNK
        assert grew["ssm_state_bytes_touched"] == 2 * sched._state_bytes * ran
    if sched._moe_rows is not None and "moe_rows" not in dict(sched._counters):
        # rows the expert matmuls ran over: those of the rungs that ran
        per_position, _ = sched._moe_rows(1)
        assert grew["moe_rows_routed"] == per_position * (
            grew["decode_slots_fed"] + grew["prefill_positions_fed"])
        whole = runs["counted_whole"]
        assert grew["moe_rows_computed"] < whole["moe_rows_computed"]


def _attention_layers(sched):
    """Layers of the scheduler's cache that hold a ``DecodeCache``."""
    return sum(1 for path, _ in jax.tree_util.tree_flatten_with_path(sched._cache)[0]
               if _leaf_name(path) == "kv_reads")


def test_a_decode_tick_counts_the_pool_it_read_where_it_lay(runs):
    """Every family's decode tick reads its stored pool as far as its slots go
    (``DecodeCache.attend_tick``) and says so on the ``program_counters`` line:
    positions live are each fed slot's, a layer; positions read are at least
    those and at most the rows a tick ran over a pool of 64 (off the chip the
    loop walks the rung's rows together, parked ones too; the kernel's own
    count is held below); a chunk hands its whole pools on and walks nothing."""
    sched, grew, whole = runs["sched"], runs["counted"], runs["counted_whole"]
    layers = _attention_layers(sched)
    assert layers >= 1 and ("kv_reads", 5) in sched._counters
    # a decode tick feeds a request's token at p, p + 1, ...: p + 1 positions live
    live = sum(len(r.prompt) + i + 1 for r in runs["reqs"] for i in range(len(r.output) - 1))
    assert grew["kv_full_positions_live_decode"] == layers * live
    assert whole["kv_full_positions_live_decode"] == layers * live
    assert layers * live <= grew["kv_full_positions_read_decode"] <= (
        layers * sched.capacity * grew["decode_slots_run"])
    assert grew["kv_full_positions_read_decode"] < whole["kv_full_positions_read_decode"] <= (
        layers * sched.capacity * whole["decode_slots_computed"])
    assert grew.get("kv_full_positions_read_prefill", 0) == 0
    assert grew.get("kv_ring_positions_read_decode", 0) == 0


@pytest.mark.parametrize("program", ["decode", "decode_rung"])
def test_the_kernels_count_is_each_fed_slots_blocks_and_parked_slots_read_nothing(
        engine, program, monkeypatch):
    """On a TPU the read is ``ops/pallas/pool_decode.py`` (here through the
    Pallas interpreter): ``kv_full_positions_read`` is the fed slots' own blocks,
    live rounded up to blocks and no more, and a tick whose slots are all
    parked reads nothing and counts nothing."""
    from deepspeed_tpu.models import common
    from deepspeed_tpu.ops.pallas import backend
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "interpret_default", lambda: True)
    block = 16
    monkeypatch.setattr(common, "decode_key_block", lambda *_: block)
    sched = _scheduler(engine)
    layers, parked = _attention_layers(sched), sched.capacity
    apply_fn = make_apply_fn(engine.module, engine._mparams)
    step = jax.jit(build_decode_step(apply_fn, False, 1.0, 0, 1.0, rung=program != "decode"))
    rows = SLOTS if program == "decode" else RUNGS[0]
    slot_ids = () if program == "decode" else (np.array([30, 1, 3, 4, 17, 9, 22, 31], np.int32),)
    for held in ([10, 63, 16, 0], []):
        write_pos = np.full(rows, parked, np.int32)
        write_pos[:len(held)] = held
        _, tok = step(sched._serve_params, sched._cache, *slot_ids, write_pos)
        counted = dict(zip(common.KV_READS, np.asarray(tok)[rows:][-5:].tolist()))
        live = sum(h + 1 for h in held)
        assert counted["kv_full_positions_live"] == layers * live
        assert counted["kv_full_positions_read"] == layers * sum(
            -(-(h + 1) // block) * block for h in held) < layers * (live + block * len(held) + 1)
        assert not (counted["kv_ring_positions_read"] or counted["kv_ring_bytes_written"])


# ---------------------------------------------------------------------------
# (e) warm-up compiled every program: a run that hops compiles nothing
# ---------------------------------------------------------------------------
def test_a_run_that_hops_between_rungs_compiles_nothing_and_runs_ahead(runs):
    assert runs["warm"] == {"prefill": 1, "prefill_rung": 1, "decode": 1, "decode_rung": 1}
    assert runs["after"] == runs["warm"]
    counted = runs["counted"]
    assert all(counted[f"{kind}_ticks_rung_{n}"] > 0 for kind in ("prefill", "decode")
               for n in RUNGS)
    # one program in flight: every program but those dispatched into an empty device
    assert counted["ticks_dispatched"] == sum(runs["sched"].ticks[k] for k in ("prefill", "decode"))
    assert 0 < counted["ticks_dispatched"] - counted["ticks_dispatched_ahead"] <= 4
    assert counted.get("ticks_settled", 0) == 0 and counted.get("slot_ticks_discarded", 0) == 0
    kinds = [r.kind for r in trace.recorder().records()
             if r.name == "program" and r.path == ("warmup",)]
    assert kinds[-4:] == ["prefill", "prefill_rung", "decode", "decode_rung"] or not kinds


# ---------------------------------------------------------------------------
# (f) the whole program is the program it was
# ---------------------------------------------------------------------------
def _parent_decode_step(apply_fn):
    """The greedy decode program as the parent of ISSUE 42 built it."""

    def decode(params, cache, write_pos):
        cache, held = without_next_tokens(cache)
        live = write_pos < slot_capacity(cache)
        tokens = jnp.where(live, held, 0)
        logits, cache = apply_fn(params, with_write_positions(cache, write_pos),
                                 tokens[:, None])
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (with_next_tokens(cache, jnp.where(live, tok, held)), with_counters(cache, tok))

    return decode


def test_the_whole_program_lowers_as_the_program_before_rungs(engine):
    apply_fn = make_apply_fn(engine.module, engine._mparams)
    cache = make_slot_cache(engine.module, SLOTS, kv_quant=True)
    operands = (engine.params, cache, np.full(SLOTS, slot_capacity(cache), np.int32))
    texts = [jax.jit(step, donate_argnums=(1,)).lower(*operands).as_text()
             for step in (build_decode_step(apply_fn, False, 1.0, 0, 1.0),
                          _parent_decode_step(apply_fn))]
    assert texts[0] == texts[1]
    assert "cache_slots" not in texts[0]
    # and a rung's program is another: it takes the slots it runs
    rung = jax.jit(build_decode_step(apply_fn, False, 1.0, 0, 1.0, rung=True),
                   donate_argnums=(1,)).lower(
        operands[0], cache, np.arange(8, dtype=np.int32), operands[2][:8])
    assert rung.as_text() != texts[0]
    with pytest.raises(TypeError, match="tokens"):   # a draft loop's feed is the whole program's
        build_decode_step(apply_fn, False, 1.0, 0, 1.0, rung=True)(
            *operands[:2], np.arange(8, dtype=np.int32), operands[2][:8], tokens=operands[2][:8])


# ---------------------------------------------------------------------------
# (g) a server that samples hops between rungs on warm-up's programs
# ---------------------------------------------------------------------------
def test_a_sampling_run_hops_between_rungs_on_the_programs_of_warmup(engine):
    """The tick's key rides behind a decode rung's operands as behind the
    whole program's; nothing is compiled after ``warmup``, every request
    draws its tokens from the vocabulary, and the same seed draws them again
    (on one ladder: a rung draws over ``[n, vocabulary]`` logits)."""
    outputs = []
    for _ in range(2):
        sched = _scheduler(engine, do_sample=True, temperature=0.8, top_k=20)
        sched.warmup()
        warm = {name: fn._cache_size() for name, fn in sched.fns.items()}
        assert warm == {"prefill": 1, "prefill_rung": 1, "decode": 1, "decode_rung": 1}
        reqs, counted = _serve(sched)
        assert all(counted[f"decode_ticks_rung_{n}"] > 0 for n in RUNGS)
        assert {name: fn._cache_size() for name, fn in sched.fns.items()} == warm
        for r in reqs:
            assert len(r.output) == r.max_new_tokens
            assert all(0 <= int(t) < 256 for t in r.output)
        outputs.append([list(r.output) for r in reqs])
    assert outputs[0] == outputs[1]
    assert len({tuple(o) for o in outputs[0]}) > 1       # draws, not one token over and over


# ---------------------------------------------------------------------------
# (h) what a decode tick's read does not take is refused by name, never dropped
# ---------------------------------------------------------------------------
def test_a_padding_mask_over_a_serving_decode_tick_is_refused_by_name():
    module = _module("olmoe-test")
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    cache, _ = without_next_tokens(make_slot_cache(module, 4, kv_quant=True))
    cache = with_write_positions(cache, jnp.asarray([3, 0, 64, 9], jnp.int32))
    ids = jnp.zeros((4, 1), jnp.int32)
    module.apply({"params": params, "cache": cache}, ids, decode=True, mutable=["cache"])
    with pytest.raises(NotImplementedError, match="padding mask over a serving decode tick"):
        module.apply({"params": params, "cache": cache}, ids, decode=True, mutable=["cache"],
                     attention_mask=jnp.ones((4, 64), jnp.int32))
    # a chunk over the same cache takes the mask to the backend, as it did
    module.apply({"params": params, "cache": cache}, jnp.zeros((4, 2), jnp.int32), decode=True,
                 mutable=["cache"], attention_mask=jnp.ones((4, 64), jnp.int32))
