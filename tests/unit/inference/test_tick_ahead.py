"""The scheduler dispatches the next tick before it reads the last one back
(ISSUE 35): one program in flight, the token a slot is fed next kept on the
device, a tick committed one step later than it is started. On the CPU, at
the test presets of every family the benchmark serves: the tokens are those
of the serial order (the same loop with every program read in the step that
dispatched it, which ``settle()`` after each step gives) and of
``generate``; a request that ends on its EOS is found out one program late
and its row discarded; whatever must read between ticks finds nothing in
flight; and a ``tick`` record's ``device_wait`` is its own kind's."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

import deepspeed_tpu
from deepspeed_tpu.inference.serving import (ACTIVE, FINISHED, PREFILL,
                                             ContinuousBatchingScheduler, Request, ServingConfig)
from deepspeed_tpu.inference.serving.programs import POOL_LEAVES, TOKEN_LEAF, _leaf_name
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils import trace

SLOTS, CHUNK, POSITIONS = 8, 8, 64
FAMILIES = ["gpt2-test", "gpt2-test-int8kv", "olmoe-test", "nemotron-h-test", "deepseek-v3-test"]
PHASES = ["admit", "build_inputs", "stamp", "dispatch", "device_wait", "commit", "heartbeat"]


def _module(family):
    """The family's model at its test preset, 64 positions a slot; the two
    that hold a share of their experts hold a quarter."""
    if family.startswith("gpt2-test"):
        from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
        return GPT2LMHeadModel(get_gpt2_config("test", n_layer=2, n_positions=POSITIONS))
    if family == "olmoe-test":
        from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config
        return LlamaForCausalLM(get_llama_config("olmoe-test", decode_cache_len=POSITIONS))
    if family == "nemotron-h-test":
        from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM, get_nemotron_h_config
        return NemotronHForCausalLM(get_nemotron_h_config(
            "nemotron-h-test", experts_held=(4, 4), decode_cache_len=POSITIONS))
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM, get_deepseek_v3_config
    return DeepseekV3ForCausalLM(get_deepseek_v3_config(
        "deepseek-v3-test", experts_held=(4, 4), decode_cache_len=POSITIONS))


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


def _engine(family):
    set_topology(None)
    module = _module(family)
    params = nn.meta.unbox(module.init(jax.random.PRNGKey(35),
                                       jnp.zeros((1, 8), jnp.int32))["params"])
    return deepspeed_tpu.init_inference(module, params=params, dtype=jnp.float32,
                                        max_out_tokens=POSITIONS,
                                        topology=MeshTopology(devices=jax.devices()[:1]))


@pytest.fixture(scope="module", params=FAMILIES)
def family_engine(request):
    yield request.param, _engine(request.param)
    set_topology(None)


@pytest.fixture(scope="module")
def engine():
    yield _engine("gpt2-test")
    set_topology(None)


def _scheduler(engine, kv_quant=False, **knobs):
    knobs.setdefault("prefix_cache", "off")
    return ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, page_size=8, kv_quant=kv_quant, prefill_chunk=CHUNK, prefill_interleave=2,
        **knobs), **({"seed": 5} if knobs.get("do_sample") else {}))


def _requests(eos=None):
    """Prompts of one chunk and of several, one that fills its slot to the
    last position (``capacity``), outputs that end at different ticks."""
    rng = np.random.default_rng(7)
    shapes = zip([5, 21, 9, 30, 3, 17, 40, 12, 8, 19, 26, 7, POSITIONS - 6],
                 [7, 3, 9, 4, 8, 5, 6, 7, 3, 9, 1, 5, 6])
    return [Request(prompt=rng.integers(0, 256, (p,)).astype(np.int32), max_new_tokens=n,
                    eos_token_id=eos) for p, n in shapes]


#: staggered: one alone (the quarter rung), two more while it prefills, six
#: at once (the whole rung), the rest into slots as they come free
ARRIVALS = {0: [0], 1: [1, 2], 6: [3], 11: [4, 5, 6, 7, 8, 9], 13: [10, 11, 12]}


def _serve(sched, reqs, serial=False):
    """Drive ``reqs`` through ``sched``; ``serial`` reads every program in
    the step that dispatched it: the order of the scheduler before this
    one. Returns the kind each step dispatched, from the counters."""
    counters, tick, programs = trace.recorder().counters, 0, []
    while any(not r.done for r in reqs):
        for i in ARRIVALS.get(tick, []):
            sched.submit(reqs[i])
        before = {k: counters.get(k, 0) for k in ("prefill_positions_run", "decode_slots_fed")}
        sched.step()
        if serial:
            sched.settle()
        programs.append(tuple(counters.get(k, 0) - v for k, v in before.items()))
        tick += 1
        assert tick < 500
    return programs


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in trace.recorder().counters.items()}


# ---------------------------------------------------------------------------
# (a) the same tokens, and the same programs, as the serial order
# ---------------------------------------------------------------------------
def test_tokens_and_programs_are_those_of_the_serial_order(family_engine):
    family, engine = family_engine
    kv_quant = family.endswith("int8kv")
    before = dict(trace.recorder().counters)
    ahead, reqs = _scheduler(engine, kv_quant), _requests()
    programs = _serve(ahead, reqs)
    counted = _delta(before)
    assert not ahead.busy
    # every program but those dispatched into an empty device ran ahead
    assert counted["ticks_dispatched"] == ahead.ticks["prefill"] + ahead.ticks["decode"]
    assert 0 < counted["ticks_dispatched"] - counted["ticks_dispatched_ahead"] <= 3
    assert counted["ticks_settled"] == 0 and counted["slot_ticks_discarded"] == 0
    if len(ahead._rungs) > 1:
        assert counted[f"prefill_ticks_rung_{ahead._rungs[0]}"] > 0     # a rung below the whole ran

    serial, reqs_serial = _scheduler(engine, kv_quant), _requests()
    programs_serial = _serve(serial, reqs_serial, serial=True)
    # every request ends by count: program for program what the serial order ran
    assert [p for p in programs if any(p)] == [p for p in programs_serial if any(p)]
    assert serial.ticks["prefill"] == ahead.ticks["prefill"]
    assert serial.ticks["decode"] == ahead.ticks["decode"]
    for got, want in zip(reqs, reqs_serial):
        assert got.state == FINISHED and len(got.output) == got.max_new_tokens
        assert got.output == want.output, got.request_id
        assert got.prefill_pos == got.prompt_len
    assert ahead.pool.counters()["used_blocks"] == 0


def test_tokens_are_those_of_generate(engine):
    sched, reqs = _scheduler(engine), _requests()
    _serve(sched, reqs)
    for r in reqs:
        ref = np.asarray(engine.generate(r.prompt[None, :], max_new_tokens=r.max_new_tokens))
        assert r.output == list(ref[0, r.prompt_len:]), r.request_id


def test_a_sampling_server_draws_what_the_serial_order_draws(engine):
    outputs = []
    for serial in (False, True):
        sched = _scheduler(engine, do_sample=True, temperature=0.9, top_k=40)
        reqs = _requests()
        _serve(sched, reqs, serial=serial)
        outputs.append([r.output for r in reqs])
    assert outputs[0] == outputs[1]
    greedy = _requests()
    _serve(_scheduler(engine), greedy)
    assert outputs[0] != [r.output for r in greedy]       # it did sample


# ---------------------------------------------------------------------------
# (b) an EOS is found out one program late
# ---------------------------------------------------------------------------
def test_an_eos_mid_stream_emits_nothing_after_it_and_discards_one_row(family_engine):
    family, engine = family_engine
    kv_quant = family.endswith("int8kv")
    plain = _requests()
    _serve(_scheduler(engine, kv_quant), plain)
    # a token some request emits in the middle of its stream, and no sooner
    index, victim = next((i, r) for i, r in enumerate(plain) if r.max_new_tokens >= 6
                         and r.output[3] not in r.output[:3])
    eos = victim.output[3]
    before = dict(trace.recorder().counters)
    sched, reqs = _scheduler(engine, kv_quant), _requests()
    reqs[index].eos_token_id = eos
    _serve(sched, reqs)
    assert reqs[index].output == victim.output[:4] and reqs[index].state == FINISHED
    for got, want in zip(reqs, plain):
        if got is not reqs[index]:
            # the discarded row touched nobody else's, nor the slot's next holder's
            assert got.output == want.output
    counted = _delta(before)
    assert counted["slot_ticks_discarded"] == 1 and counted["ticks_settled"] == 0
    assert sched.pool.counters()["used_blocks"] == 0 and sched._inflight is None


def test_an_eos_as_the_first_token_is_found_out_too(engine):
    probe = Request(prompt=np.arange(3, 12, dtype=np.int32), max_new_tokens=4)
    sched = _scheduler(engine)
    sched.submit(probe)
    sched.run_until_drained()
    before = dict(trace.recorder().counters)
    req = Request(prompt=probe.prompt, max_new_tokens=4, eos_token_id=probe.output[0])
    sched.submit(req)
    sched.run_until_drained()
    assert req.output == probe.output[:1] and req.state == FINISHED
    assert _delta(before)["slot_ticks_discarded"] == 1


# ---------------------------------------------------------------------------
# (c) whoever reads between ticks finds nothing in flight
# ---------------------------------------------------------------------------
def _midstream(engine, **knobs):
    """A scheduler a few steps into three requests, one program in flight
    (none where it must read each in its own step)."""
    sched = _scheduler(engine, **knobs)
    reqs = [Request(prompt=np.arange(1, n, dtype=np.int32), max_new_tokens=8) for n in (6, 11, 19)]
    for r in reqs:
        sched.submit(r)
    for _ in range(4):
        sched.step()
    return sched, reqs


def _agree(sched):
    """The requests' fields and the scheduler's own count agree."""
    for slot, req in enumerate(sched._slot_req):
        if req is not None:
            assert req.prefill_pos == sched._fed[slot] and len(req.output) == sched._sent[slot]
            assert sched._lengths[slot] == req.prefill_pos + max(0, len(req.output) - 1)
            assert req.state == (ACTIVE if req.output else PREFILL)


def test_a_step_leaves_one_program_in_flight_and_settle_reads_it(engine):
    before = dict(trace.recorder().counters)
    sched, reqs = _midstream(engine)
    assert sched._inflight is not None
    assert any(len(r.output) < sched._sent[i] for i, r in enumerate(sched._slot_req) if r)
    assert sched.settle() in ("prefill", "decode") and sched._inflight is None
    assert sched.settle() is None
    _agree(sched)
    counted = _delta(before)
    assert counted["ticks_settled"] == counted["ticks_settled_drain"] == 1
    sched.run_until_drained()
    assert all(len(r.output) == 8 for r in reqs)


def test_run_until_drained_leaves_nothing_in_flight_when_cut_short(engine):
    before = dict(trace.recorder().counters)
    sched = _scheduler(engine)
    req = Request(prompt=np.arange(1, 30, dtype=np.int32), max_new_tokens=8)
    sched.submit(req)
    assert sched.run_until_drained(max_ticks=3) == 3
    assert sched._inflight is None and req.prefill_pos == 3 * CHUNK
    assert _delta(before)["ticks_settled_drain"] == 1
    sched.run_until_drained()
    assert req.state == FINISHED and sched.step() == "idle"
    assert _delta(before)["ticks_settled_drain"] == 1     # a loop that ran out settles nothing


def test_export_and_swap_read_what_is_in_flight_first(engine):
    before = dict(trace.recorder().counters)
    sched, reqs = _midstream(engine)
    payloads = sched.export_inflight(release=False)
    assert sched._inflight is None
    _agree(sched)
    assert {p["state"] for p in payloads} == {PREFILL, ACTIVE}      # one prompt is still coming in
    for payload, req in zip(payloads, reqs):
        assert payload["output"] == req.output
        assert payload["next_token"] == (req.output[-1] if req.output else 0)
        assert payload["length"] == req.prefill_pos + max(0, len(req.output) - 1)
    assert _delta(before)["ticks_settled_export"] == 1
    sched.step()
    assert sched._inflight is not None
    sched.swap_served_params(engine.params)
    assert sched._inflight is None and _delta(before)["ticks_settled_swap"] == 1
    _agree(sched)
    # a peer takes the export up where it was left, token for token
    peer = _scheduler(engine)
    moved = [peer.admit_migrated(p) for p in payloads]
    peer.run_until_drained()
    whole = _scheduler(engine)
    fresh = [Request(prompt=r.prompt, max_new_tokens=8) for r in reqs]
    for r in fresh:
        whole.submit(r)
    whole.run_until_drained()
    assert [m.output for m in moved] == [r.output for r in fresh]


def test_a_prefix_cache_reads_every_program_in_its_own_step(engine):
    before = dict(trace.recorder().counters)
    sched, reqs = _midstream(engine, prefix_cache="on")
    assert sched._inflight is None
    _agree(sched)
    sched.run_until_drained()
    counted = _delta(before)
    assert counted["ticks_dispatched_ahead"] == 0
    assert counted["ticks_settled_prefix"] == counted["ticks_dispatched"] == counted["ticks_settled"]
    ahead = [Request(prompt=r.prompt, max_new_tokens=8) for r in reqs]
    off = _scheduler(engine)
    for r in ahead:
        off.submit(r)
    off.run_until_drained()
    assert [r.output for r in reqs] == [r.output for r in ahead]


def test_a_drafter_reads_every_program_in_its_own_step(engine):
    before = dict(trace.recorder().counters)
    sched = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=SLOTS, page_size=8, prefill_chunk=CHUNK, prefix_cache="off",
                              speculation={"enabled": True, "k": 2}),
        drafter=(engine.module, engine.params))
    reqs = [Request(prompt=np.arange(1, n, dtype=np.int32), max_new_tokens=8) for n in (6, 11, 19)]
    for r in reqs:
        sched.submit(r)
    for _ in range(4):
        sched.step()
        assert sched._inflight is None
    sched.run_until_drained()
    counted = _delta(before)
    assert sched.ticks["spec"] > 0 and counted["ticks_dispatched_ahead"] == 0
    assert counted["ticks_settled_spec"] == counted["ticks_dispatched"]
    for r in reqs:
        ref = np.asarray(engine.generate(r.prompt[None, :], max_new_tokens=8))
        assert r.output == list(ref[0, r.prompt_len:])


# ---------------------------------------------------------------------------
# (d) a tick's spans are its own program's
# ---------------------------------------------------------------------------
def test_a_ticks_device_wait_is_its_own_kinds(engine):
    sched, reqs = _scheduler(engine), _requests()
    reads = []          # the kind of every program read, in order
    read_back = sched._read_back
    sched._read_back = lambda tok, kind: (reads.append(kind), read_back(tok, kind))[1]
    _serve(sched, reqs)
    records = trace.recorder().records(sched._source)
    ticks = [r for r in records if r.name == "tick"]
    assert [t.uid for t in ticks] == list(range(1, len(ticks) + 1))
    waited = []
    for tick in ticks:
        children = sorted((r for r in records if r.uid == tick.uid and r.path == ("tick",)),
                          key=lambda r: r.seq)
        names = [c.name for c in children]
        assert names.count("device_wait") <= 1 and names.count("commit") == names.count("device_wait")
        if "device_wait" in names:
            waited.append(tick.kind)
        assert all(a.end <= b.start for a, b in zip(children, children[1:]))
        assert all(tick.start <= c.start and c.end <= tick.end for c in children)
        assert sum(c.dur for c in children) <= tick.dur
        if tick.kind == "idle":
            assert names == ["admit", "heartbeat"]
        else:       # the whole seven, but for a tick with no program before or after it
            assert names in (PHASES, PHASES[:4] + PHASES[6:], [PHASES[0]] + PHASES[4:]), tick
    # a tick that waited has the kind of the program it read: the reads in order
    assert waited == reads and len(reads) == sched.ticks["prefill"] + sched.ticks["decode"]
    by_kind = {}
    for tick in ticks:
        by_kind.setdefault(tick.kind, set()).update(
            r.name for r in records if r.uid == tick.uid and r.path == ("tick",))
    assert by_kind["prefill"] == by_kind["decode"] == set(PHASES)


def test_the_cache_before_a_tick_is_deleted_with_a_program_in_flight(engine):
    """PR 25's case (d): a tick donates the cache it is handed, in flight or
    not: nothing of the tick before it is left on the device but its tokens."""
    sched, _ = _midstream(engine)
    assert sched._inflight is not None
    def pools(cache):
        return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
                if _leaf_name(path) in POOL_LEAVES]

    held = pools(sched._cache)
    sched.step()
    assert sched._inflight is not None
    assert held and all(leaf.is_deleted() for leaf in held)
    assert not any(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(sched._cache))
    assert sched._cache[TOKEN_LEAF].shape == (SLOTS,) and sched._cache[TOKEN_LEAF].dtype == jnp.int32
    sched.run_until_drained()
