"""The serving programs take the per-slot write positions as ONE operand
(ISSUE 25): the host writes nothing into the cache between ticks, a tick
issues at most one explicit host-to-device transfer, no program retraces
as slots join, leave or park, writes land where the operand says, parked
slots write nothing, the cache still donates tick to tick, and greedy
tokens equal offline ``engine.generate``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (FINISHED, ContinuousBatchingScheduler,
                                             Request, ServingConfig, make_slot_cache,
                                             slot_capacity)
from deepspeed_tpu.inference.serving.programs import (INDEX_LEAVES, KV_LEAVES, _leaf_name,
                                                      build_decode_step, build_prefill_step,
                                                      counter_widths, make_apply_fn)
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.models.common import slot_pool_rows
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils import trace

SLOTS, CHUNK = 4, 8


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


@pytest.fixture(scope="module")
def engine_cfg():
    set_topology(None)
    cfg = get_gpt2_config("test", n_layer=2, n_positions=128)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    engine = InferenceEngine(GPT2LMHeadModel(cfg),
                             DeepSpeedInferenceConfig(replace_with_kernel_inject=False),
                             topology=topo)
    yield engine, cfg
    set_topology(None)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32) for p in lengths]


def _drafter(cfg):
    from flax.linen import meta
    d_model = GPT2LMHeadModel(get_gpt2_config("test", n_layer=1,
                                              n_positions=cfg.n_positions))
    d_params = meta.unbox(d_model.init(jax.random.PRNGKey(1),
                                       jnp.zeros((1, 8), jnp.int32))["params"])
    # as initialised it agrees with the tiny target on every token; with its
    # matrices scaled it is accepted some of the time, so rollbacks happen
    return d_model, jax.tree.map(lambda x: x * 1.5 if x.ndim == 2 else x, d_params)


def _scheduler(engine, cfg, kv_quant, spec_k):
    scfg = ServingConfig(slots=SLOTS, prefill_chunk=CHUNK, kv_quant=kv_quant,
                         speculation={"enabled": bool(spec_k), "k": spec_k or 4})
    return ContinuousBatchingScheduler(engine, scfg,
                                       drafter=_drafter(cfg) if spec_k else None)


def _kv_leaves(cache):
    return {jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if _leaf_name(path) in KV_LEAVES or _leaf_name(path).endswith("_scale")}


# ---------------------------------------------------------------------------
# (a) at most one explicit put a tick, and one program per jitted function
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec"])
def test_ticks_put_at_most_once_and_never_retrace(engine_cfg, spec_k):
    """Staggered arrivals with short and long prompts: the set of busy
    slots, the parked slots and every position change from tick to tick,
    and the prefill ticks hop between the rungs of the prefill program
    (ISSUE 33: one slot, and all four), yet each tick issues at most
    one explicit transfer (none without speculation) and every program
    keeps the entries warm-up gave it: one, and one a rung below the whole
    behind ``prefill_rung``."""
    engine, cfg = engine_cfg
    sched = _scheduler(engine, cfg, kv_quant=True, spec_k=spec_k)
    sched.warmup()
    assert sched._rungs == (1, SLOTS)
    counters = trace.recorder().counters
    rung_ticks = {n: counters.get(f"prefill_ticks_rung_{n}", 0) for n in sched._rungs}
    reqs = [Request(prompt=p, max_new_tokens=n) for p, n in
            zip(_prompts(cfg, [5, 21, 9, 30, 3, 17], seed=5), [7, 3, 9, 4, 8, 5])]
    arrivals = {0: [0], 1: [1, 2], 6: [3], 11: [4, 5]}
    kinds, tick = set(), 0
    while any(not r.done for r in reqs):
        for i in arrivals.get(tick, []):
            sched.submit(reqs[i])
        before = counters["tick_input_puts"]
        kind = sched.step()
        puts = counters["tick_input_puts"] - before
        assert puts == (1 if kind == "spec" else 0), (tick, kind, puts)
        kinds.add(kind)
        tick += 1
        assert tick < 500
    assert {"prefill", "spec" if spec_k else "decode"} <= kinds
    assert all(counters.get(f"prefill_ticks_rung_{n}", 0) > was for n, was in rung_ticks.items())
    for fns in (sched.fns,) + ((sched.dfns,) if spec_k else ()):
        for name, fn in fns.items():
            dead = bool(spec_k) and fns is sched.fns and name == "decode"
            entries = 0 if dead else len(sched._rungs) - 1 if name == "prefill_rung" else 1
            assert fn._cache_size() == entries, (name, fn._cache_size())
    # which takes a cache that comes back placed as the fresh one was, the
    # int8 scale leaves (rank 3, as some weights are) included
    for path, leaf in jax.tree_util.tree_flatten_with_path(sched._cache)[0]:
        assert leaf.sharding == sched._placement, (jax.tree_util.keystr(path), leaf.sharding)


# ---------------------------------------------------------------------------
# (b) writes land at the operand's positions; parked slots write nothing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8kv"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_rows_land_at_write_pos_and_parked_slots_write_nothing(engine_cfg, program, kv_quant):
    engine, cfg = engine_cfg
    apply_fn = make_apply_fn(engine.module, engine._mparams)
    cache = make_slot_cache(engine.module, SLOTS, kv_quant=kv_quant)
    parked = slot_capacity(cache)
    # a pool that is nowhere zero, so a dropped write and a write are told apart
    cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf if _leaf_name(path) in INDEX_LEAVES
        else jnp.full(leaf.shape, 3, leaf.dtype), cache)
    before = {k: np.array(v) for k, v in _kv_leaves(cache).items()}  # copies: the cache is donated
    write_pos = np.array([3, parked, 40, parked], np.int32)
    rng = np.random.default_rng(2)
    if program == "decode":
        rows = 1
        step = jax.jit(build_decode_step(apply_fn, False, 1.0, 0, 1.0), donate_argnums=(1,))
        # the tokens by name, in the place of those the cache holds
        inputs, fed = (), {"tokens": rng.integers(1, cfg.vocab_size, SLOTS).astype(np.int32)}
    else:
        rows = CHUNK
        step = jax.jit(build_prefill_step(apply_fn, False, 1.0, 0, 1.0), donate_argnums=(1,))
        inputs, fed = (rng.integers(1, cfg.vocab_size, (SLOTS, CHUNK)).astype(np.int32),
                       np.full(SLOTS, CHUNK - 1, np.int32)), {}
    behind = sum(n for _, n in counter_widths(cache))
    new_cache, tok = step(engine.params, cache, write_pos, *inputs, **fed)
    # a step fed its tokens by name returns tokens alone; any other, the
    # cache's counters behind them (a ``DecodeCache`` layer's ``kv_reads``)
    assert behind == 5 and tok.shape == (SLOTS + (0 if fed else behind),)
    after = {k: np.asarray(v) for k, v in _kv_leaves(new_cache).items()}
    assert after.keys() == before.keys() and len(after) == (8 if kv_quant else 4)
    for key, old in before.items():
        for slot, pos in enumerate(write_pos):
            # position-major rows, whatever the stored form
            new, was = (slot_pool_rows(leaf, slot, 0, parked) for leaf in (after[key], old))
            if pos == parked:
                np.testing.assert_array_equal(new, was, err_msg=f"{key} slot {slot}")
                continue
            written = np.zeros(parked, bool)
            written[pos:pos + rows] = True
            np.testing.assert_array_equal(new[~written], was[~written],
                                          err_msg=f"{key} slot {slot}")
            changed = (new[written] != was[written]).reshape(rows, -1).any(axis=1)
            assert changed.all(), (key, slot, changed)
    # what the carried index leaves held never mattered: each comes back as
    # the operand advanced by the rows fed
    for path, leaf in jax.tree_util.tree_flatten_with_path(new_cache)[0]:
        if _leaf_name(path) in INDEX_LEAVES:
            np.testing.assert_array_equal(np.asarray(leaf), write_pos + rows)


# ---------------------------------------------------------------------------
# (c) greedy tokens equal the offline engine's
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec"])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8kv"])
def test_scheduler_tokens_equal_generate(engine_cfg, kv_quant, spec_k):
    """Also under speculation, whose rejected drafts leave KV rows behind
    that the next tick's operand simply writes over (rollback is free)."""
    engine, cfg = engine_cfg
    sched = _scheduler(engine, cfg, kv_quant=kv_quant, spec_k=spec_k)
    reqs = [Request(prompt=p, max_new_tokens=8)
            for p in _prompts(cfg, [5, 19, 9, 26, 12], seed=11)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained(max_ticks=500)
    assert all(r.state == FINISHED for r in reqs)
    if spec_k:
        assert 0 < sched.accepted_total < sched.drafted_total
    for r in reqs:
        ref = np.asarray(engine.generate(r.prompt[None, :], max_new_tokens=8))
        assert r.output == list(ref[0, r.prompt_len:]), r.request_id


# ---------------------------------------------------------------------------
# (d) the cache is donated tick to tick
# ---------------------------------------------------------------------------
def test_cache_is_donated_every_tick(engine_cfg):
    engine, cfg = engine_cfg
    sched = _scheduler(engine, cfg, kv_quant=True, spec_k=0)
    for p in _prompts(cfg, [11, 4], seed=7):
        sched.submit(Request(prompt=p, max_new_tokens=5))
    kinds = []
    while sched.in_flight or len(sched.queue):
        held = _kv_leaves(sched._cache)
        kinds.append(sched.step())
        assert all(leaf.is_deleted() for leaf in held.values()), kinds
        assert not any(leaf.is_deleted() for leaf in _kv_leaves(sched._cache).values())
    assert {"prefill", "decode"} <= set(kinds)


# ---------------------------------------------------------------------------
# (e) the construction-time probe is the decode program's own trace
# ---------------------------------------------------------------------------
def test_the_probe_is_the_decode_programs_one_trace(engine_cfg, monkeypatch):
    """A scheduler traces its decode program once, at construction, where it
    finds out whether the family can serve; ``warmup`` and the decode ticks
    after it find that trace and make no second one."""
    from deepspeed_tpu.inference.serving import programs
    engine, cfg = engine_cfg
    built, traces = programs.build_decode_step, []

    def counting(*args, **kwargs):
        step = built(*args, **kwargs)

        def counted(*operands):
            traces.append(1)
            return step(*operands)

        return counted

    monkeypatch.setattr(programs, "build_decode_step", counting)
    # a temperature no other test serves at: programs of this scheduler's own
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, prefill_chunk=CHUNK, temperature=0.9))
    assert len(traces) == 1
    sched.warmup()
    for p in _prompts(cfg, [11, 4], seed=9):
        sched.submit(Request(prompt=p, max_new_tokens=5))
    sched.run_until_drained()
    assert sched.ticks["decode"] > 0 and len(traces) == 1
    assert sched.fns["decode"]._cache_size() == 1


@pytest.mark.parametrize("fault", ["model", "sampler"])
def test_the_probe_names_the_family_only_where_the_model_refused(engine_cfg, monkeypatch, fault):
    """A decode path that cannot take a [slots] index is refused with the
    family's name; a fault of what surrounds the model in the decode program
    comes out as itself."""
    from deepspeed_tpu.inference import engine as inference_engine
    from deepspeed_tpu.inference.serving import programs
    engine, _ = engine_cfg
    if fault == "model":
        made = programs.make_apply_fn

        def refusing(*args, **kwargs):
            apply_fn = made(*args, **kwargs)

            def apply(params, cache, ids):
                if ids.shape[1] == 1:
                    raise TypeError("cache_index is one number a batch")
                return apply_fn(params, cache, ids)

            return apply

        monkeypatch.setattr(programs, "make_apply_fn", refusing)
        raised, match = NotImplementedError, "GPT2LMHeadModel does not support the per-slot"
    else:
        def broken(*args, **kwargs):
            raise ValueError("no such sampler")

        monkeypatch.setattr(inference_engine, "sample_logits", broken)
        raised, match = ValueError, "no such sampler"
    with pytest.raises(raised, match=match):
        ContinuousBatchingScheduler(engine, ServingConfig(
            slots=SLOTS, prefill_chunk=CHUNK, do_sample=True,
            temperature=0.8 if fault == "model" else 0.7))

