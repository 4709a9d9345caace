"""What the serving scheduler writes into the program's recorder
(``utils/trace.py``), on a simulated clock: the ``tick`` span and its
host phases, a request's two waits, and the counters of work fed against
work computed."""

import numpy as np
import pytest

import jax

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler, Request,
                                             ServingConfig)
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils import trace

PHASES = ["admit", "build_inputs", "stamp", "dispatch", "device_wait", "commit", "heartbeat"]
LENGTHS = [5, 20, 9, 33, 7, 13]
SLOTS, CHUNK = 4, 8


class SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt=1.0):
        self.t += dt


@pytest.fixture(scope="module")
def engine():
    set_topology(None)
    cfg = get_gpt2_config("test", n_layer=2, n_positions=128)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    yield InferenceEngine(GPT2LMHeadModel(cfg),
                          DeepSpeedInferenceConfig(replace_with_kernel_inject=False),
                          topology=topo)
    set_topology(None)


def _serve(engine, prefix_cache="off", telemetry=None):
    """Six requests arriving over the first ticks through a four-slot
    scheduler; returns (scheduler, requests, its records, counter deltas)."""
    rec = trace.recorder()
    before = dict(rec.counters)
    clock = SimClock()
    sched = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=SLOTS, prefill_chunk=CHUNK, page_size=16,
                              prefix_cache=prefix_cache),
        clock=clock, telemetry=telemetry)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, 256, (n,)).astype(np.int32), max_new_tokens=6)
            for n in LENGTHS]
    arrivals = {0: [0, 1], 2: [2, 3], 5: [4, 5]}
    sched.step()                        # an idle tick: nothing has arrived
    clock.advance()
    tick = 0
    while any(not r.done for r in reqs):
        for i in arrivals.get(tick, []):
            sched.submit(reqs[i])
        sched.step()
        clock.advance()
        tick += 1
        assert tick < 500
    counters = {k: v - before.get(k, 0) for k, v in rec.counters.items()}
    return sched, reqs, rec.records(sched._source), counters


@pytest.fixture(scope="module")
def served(engine):
    return _serve(engine)


def _ticks(records):
    """[(tick record, its direct children in the order they ran)]."""
    out = []
    for tick in (r for r in records if r.name == "tick"):
        children = sorted((r for r in records if r.uid == tick.uid and r.path == ("tick",)),
                          key=lambda r: r.seq)
        out.append((tick, children))
    return out


def test_every_working_tick_has_the_named_children_in_order(served):
    sched, _, records, _ = served
    ticks = _ticks(records)
    assert [t.uid for t, _ in ticks] == list(range(1, len(ticks) + 1))
    kinds = [t.kind for t, _ in ticks]
    assert kinds[0] == "idle" and {"prefill", "decode"} <= set(kinds)
    # a tick ends with the read-back of a program and has that program's kind
    # (ISSUE 35): one tick a program, and one more that dispatched the first
    # program of a busy stretch into an empty device and read nothing
    read = [t.kind for t, children in ticks if any(c.name == "device_wait" for c in children)]
    assert {k: read.count(k) for k in set(read) | {"idle"}} == \
        {k: v for k, v in sched.ticks.items() if v} | {"idle": 0}
    assert kinds.count("idle") == sched.ticks["idle"] and len(kinds) == len(read) + 2
    for tick, children in ticks:
        names = [c.name for c in children]
        if tick.kind == "idle":
            assert names == ["admit", "heartbeat"]
        else:   # no program before it to read, or none after it to dispatch
            assert names in (PHASES, PHASES[:4] + PHASES[6:], PHASES[:1] + PHASES[4:]), tick
        assert all(c.parent == "tick" and c.source == sched._source for c in children)


def test_phase_durations_sum_to_no_more_than_their_tick(served):
    _, _, records, _ = served
    for tick, children in _ticks(records):
        assert all(tick.start <= c.start <= c.end <= tick.end for c in children)
        assert all(a.end <= b.start for a, b in zip(children, children[1:]))     # one after another
        assert sum(c.dur for c in children) <= tick.dur


def test_admit_time_lies_between_arrival_and_first_token(served):
    _, reqs, _, _ = served
    for r in reqs:
        assert r.arrival_time <= r.admit_time <= r.first_token_time
    assert any(r.admit_time > r.arrival_time for r in reqs)      # four slots, six requests: some queued


def test_queue_wait_plus_prefill_wait_is_the_time_to_first_token(served):
    _, reqs, records, _ = served
    waits = {name: {r.uid: r for r in records if r.name == name}
             for name in ("queue_wait", "prefill_wait")}
    assert set(waits["queue_wait"]) == set(waits["prefill_wait"]) == {r.request_id for r in reqs}
    for r in reqs:
        queue, prefill = waits["queue_wait"][r.request_id], waits["prefill_wait"][r.request_id]
        assert (queue.start, queue.end) == (r.arrival_time, r.admit_time)
        assert (prefill.start, prefill.end) == (r.admit_time, r.first_token_time)
        assert queue.dur + prefill.dur == r.ttft
        # ring only, filed under the phase that wrote them, under the request's own identifier
        assert queue.path == ("tick", "admit") and prefill.path == ("tick", "commit")


def test_prefill_counters_are_tokens_fed_against_positions_computed(served):
    sched, reqs, _, counters = served
    assert counters["prefill_positions_fed"] == sum(LENGTHS)
    assert counters["prefill_positions_computed"] == sched.ticks["prefill"] * SLOTS * CHUNK
    assert counters["prefill_positions_fed"] < counters["prefill_positions_computed"]


def test_decode_counters_are_slots_fed_against_slots_computed(served):
    sched, reqs, _, counters = served
    # a request's first token comes out of its last prefill tick, the rest out of decode ticks
    assert counters["decode_slots_fed"] == sum(len(r.output) - 1 for r in reqs)
    assert counters["decode_slots_computed"] == sched.ticks["decode"] * SLOTS


def test_kv_write_counters_are_positions_handed_against_positions_rewritten(served):
    sched, reqs, _, counters = served
    # a prefill tick hands every fed slot a whole chunk (a short one is
    # padded), a decode tick one token; off the TPU the write is a scatter,
    # which rewrites just those (the in-place write's whole windows:
    # test_kv_pool_write.py)
    chunks = sum(-(-n // CHUNK) for n in LENGTHS)
    assert counters["kv_positions_written"] == chunks * CHUNK + counters["decode_slots_fed"]
    assert counters["kv_window_positions_touched"] == counters["kv_positions_written"]


def test_a_new_program_set_is_counted_once(engine):
    rec = trace.recorder()
    start = rec.counters.get("serve_program_builds", 0)
    config = ServingConfig(slots=2, prefill_chunk=4, prefix_cache="off")     # no other test's key
    ContinuousBatchingScheduler(engine, config)
    assert rec.counters["serve_program_builds"] == start + 1
    ContinuousBatchingScheduler(engine, config)              # the engine's cache hits
    assert rec.counters["serve_program_builds"] == start + 1


def test_publish_is_a_span_under_commit_when_the_prefix_cache_is_on(engine):
    _, _, records, _ = _serve(engine, prefix_cache="on")
    publishes = [r for r in records if r.name == "publish"]
    assert publishes and all(r.path == ("tick", "commit") for r in publishes)
    for tick, children in _ticks(records):      # the direct children stay the named ones
        assert [c.name for c in children] == (["admit", "heartbeat"] if tick.kind == "idle"
                                              else PHASES)


def test_an_attached_sink_flushes_its_own_schedulers_records_only(engine, tmp_path):
    """Two schedulers in one process, one with a sink: its window holds
    its own ticks, not the other's."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import RuntimeTelemetry, read_events

    tel = RuntimeTelemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                           job_name="serve", flush_interval_steps=10 ** 6))
    other, _, _, _ = _serve(engine)
    sched, _, records, _ = _serve(engine, telemetry=tel)
    assert sched._source == tel.source != other._source
    tel.flush_window(step=sum(sched.ticks.values()))
    tel.close()
    events = read_events(str(tmp_path / "serve" / "telemetry.jsonl"))
    spans = [s for e in events if e["event"] == "spans" for s in e["spans"]]
    assert len(spans) == len(records)
    assert sum(s["name"] == "tick" for s in spans) == sum(r.name == "tick" for r in records)
    window = [e for e in events if e["event"] == "step_window"][-1]
    assert window["phases"]["device_wait"]["count"] == \
        sched.ticks["prefill"] + sched.ticks["decode"]
    assert window["metrics"]["counters"]["prefill_positions_fed"] >= sum(LENGTHS)
    # what the ring had pushed out when the window was written, beside the counters
    assert window["metrics"]["counters"]["ring_records_dropped"] == trace.recorder().dropped


# -- ``dispatch`` by part, what a call is handed, the device under it, a stalled tick (ISSUE 53) --

def test_launch_and_account_lie_under_dispatch_in_both_kinds_of_tick_and_on_a_rung(served):
    sched, _, records, counters = served
    assert counters["prefill_ticks_rung_1"] > 0 and counters[f"prefill_ticks_rung_{SLOTS}"] > 0
    seen = set()
    for tick, children in _ticks(records):
        dispatches = [c for c in children if c.name == "dispatch"]
        inner = sorted((r for r in records if r.uid == tick.uid and r.path == ("tick", "dispatch")),
                       key=lambda r: r.seq)
        if not dispatches:
            assert inner == []
            continue
        (dispatch,) = dispatches
        assert [r.name for r in inner] == ["launch", "account"]
        launch, account = inner
        assert dispatch.start <= launch.start <= launch.end <= account.start <= account.end \
            <= dispatch.end
        assert launch.source == account.source == sched._source
        assert launch.kind in ("prefill", "decode") and account.kind is None
        seen.add(launch.kind)
    assert seen == {"prefill", "decode"}
    # every launch's wall and CPU time, by the kind of program
    for kind in ("", "_prefill", "_decode"):
        assert 0 <= counters["span_cpu_us_launch" + kind]
        assert 0 < counters["span_wall_us_launch" + kind]
    assert counters["span_wall_us_launch"] == (counters["span_wall_us_launch_prefill"]
                                               + counters["span_wall_us_launch_decode"])
    # the process's launches are this scheduler's and any before it: none is skipped
    launches = [r for r in records if r.name == "launch" and r.source == sched._source]
    assert len(launches) > 16
    assert counters["span_wall_us_launch"] >= int(sum(r.dur for r in launches) * 1e6) - len(launches)


def test_the_gauges_say_what_a_call_is_handed_and_a_second_scheduler_does_not_double_them(engine):
    rec = trace.recorder()
    config = ServingConfig(slots=SLOTS, prefill_chunk=CHUNK, page_size=16, prefix_cache="off")
    first = ContinuousBatchingScheduler(engine, config)
    handed = jax.tree_util.tree_leaves((first._serve_params, first._cache))
    assert rec.counters["program_operand_leaves"] == len(handed) > 10
    assert rec.counters["program_operand_bytes"] == sum(leaf.nbytes for leaf in handed)
    assert first.kv_quant
    plain = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=SLOTS, prefill_chunk=CHUNK, page_size=16, prefix_cache="off", kv_quant=False))
    fewer = jax.tree_util.tree_leaves((plain._serve_params, plain._cache))
    assert len(fewer) < len(handed)                 # no scale beside a pool
    assert rec.counters["program_operand_leaves"] == len(fewer)    # the newest's: set, not added
    assert rec.counters["program_operand_bytes"] == sum(leaf.nbytes for leaf in fewer)
    first.warmup()
    # the host arrays a tick hands each program: positions; and ids and the last index; and rows
    assert {name: rec.counters["program_host_operands_" + name]
            for name in ("decode", "prefill", "prefill_rung")} == \
        {"decode": 1, "prefill": 3, "prefill_rung": 4}


class _Token:
    """A program's tokens whose readiness, and whose read-back's length, are scripted."""

    def __init__(self, tok, looks=(), read_s=0.0):
        self.tok, self.script, self.looks, self.read_s = tok, list(looks), 0, read_s

    def is_ready(self):
        self.looks += 1
        return self.script.pop(0)

    def __array__(self, dtype=None, copy=None):
        import time
        time.sleep(self.read_s)
        return np.asarray(self.tok)


def _decoding(engine, telemetry=None, ticks=0):
    """A scheduler with a decode program in flight, after ``ticks`` decode ticks at least."""
    sched = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=SLOTS, prefill_chunk=CHUNK, page_size=16, prefix_cache="off"),
        telemetry=telemetry)
    for n in (5, 7):
        sched.submit(Request(prompt=np.arange(n, dtype=np.int32), max_new_tokens=40))
    while not (sched._inflight is not None and sched._inflight.kind == "decode"
               and sched.ticks["decode"] > ticks):
        sched.step()
    return sched


@pytest.mark.parametrize("looks, phase", [
    ([True], "admit"), ([False, True], "build_inputs"), ([False, False, True], "launch"),
    ([False, False, False], None),
], ids=["ended-before-the-build", "ended-before-the-dispatch", "ended-under-the-launch", "fed-in-time"])
def test_a_program_looks_at_the_one_in_flight_and_files_a_dry_device_under_the_phase(
        engine, looks, phase):
    sched = _decoding(engine)
    rec = trace.recorder()
    before = dict(rec.counters)
    sched._inflight.tok = token = _Token(sched._inflight.tok, looks)
    sched.step()
    uid = sched._tick_no
    delta = {k: v - before.get(k, 0) for k, v in rec.counters.items()
             if k.startswith(("ticks_device_dry", "device_dry_us", "ticks_dispatched"))
             and v != before.get(k, 0)}
    dry = [r for r in rec.records(sched._source) if r.name == "device_dry" and r.uid == uid]
    assert token.looks == len(looks) and token.script == []     # no look after the one that saw it
    if phase is None:
        assert dry == [] and delta == {"ticks_dispatched": 1, "ticks_dispatched_ahead": 1}
        return
    assert delta.pop("ticks_dispatched") == delta.pop("ticks_dispatched_ahead") == 1
    least, most = delta.pop("device_dry_us_min", 0), delta.pop("device_dry_us_max")
    assert delta == {"ticks_device_dry": 1, f"ticks_device_dry_in_{phase}": 1}
    assert 0 <= least <= most
    (r,) = dry
    spans = {s.name: s for s in rec.records(sched._source) if s.uid == uid and s.name != "device_dry"}
    launch, tick = spans["launch"], spans["tick"]
    # from the look that saw it ended to the launch's return; beside the tick, not a phase of it
    seen = {"admit": spans["build_inputs"].start, "build_inputs": spans["dispatch"].start,
            "launch": launch.end}[phase]
    looked_before = {"admit": tick.start, "build_inputs": spans["build_inputs"].start,
                     "launch": spans["dispatch"].start}[phase]
    assert (r.kind, r.start, r.end, r.path) == (phase, seen, launch.end, ())
    assert int(r.dur * 1e6) == least and int((launch.end - looked_before) * 1e6) == most
    assert tick.start <= r.start <= r.end <= tick.end


def test_a_tick_dispatched_with_nothing_in_flight_is_left_out(engine):
    rec = trace.recorder()
    sched = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=SLOTS, prefill_chunk=CHUNK, page_size=16, prefix_cache="off"))
    before = dict(rec.counters)
    sched.submit(Request(prompt=np.arange(5, dtype=np.int32), max_new_tokens=4))
    assert sched.step() == "prefill" and sched._inflight is not None     # into an empty scheduler
    assert rec.counters["ticks_dispatched"] == before["ticks_dispatched"] + 1
    for name in ("ticks_dispatched_ahead", "ticks_device_dry"):
        assert rec.counters[name] == before[name]
    assert not [r for r in rec.records(sched._source) if r.name == "device_dry"]
    # nor does a scheduler that reads every program in its own step ever look
    serial, _, records, counters = _serve(engine, prefix_cache="on")
    assert counters["ticks_dispatched"] > 0 and counters["ticks_device_dry"] == 0
    assert not [r for r in records if r.name == "device_dry"]


def test_a_stalled_tick_says_so_and_the_sink_carries_it(engine, tmp_path):
    """A read-back held half a second under a steady scheduler: one stalled
    tick, named by the phase it hung under; ``RuntimeTelemetry``'s JSONL
    carries the ``stall`` and a ``device_dry`` like any record."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import RuntimeTelemetry, read_events

    tel = RuntimeTelemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                           job_name="serve", flush_interval_steps=10 ** 6))
    rec = trace.recorder()
    sched = _decoding(engine, tel, ticks=12)
    assert not [r for r in rec.records(sched._source) if r.name == "stall"]     # steady so far
    stalled_before = rec.counters["units_stalled_tick"]
    sched._inflight.tok = _Token(sched._inflight.tok, [True], read_s=0.5)
    assert sched.step() == "decode"
    uid = sched._tick_no
    assert rec.counters["units_stalled_tick"] == stalled_before + 1
    assert rec.counters["stall_us_tick"] >= 0.5e6
    (stall,) = [r for r in rec.records(sched._source) if r.name == "stall"]
    assert (stall.uid, stall.kind) == (uid, "decode:device_wait") and stall.dur >= 0.5
    for _ in range(5):          # the ticks after it are ticks like those before it
        sched.step()
    assert rec.counters["units_stalled_tick"] == stalled_before + 1
    tel.flush_window(step=sched._tick_no)
    tel.close()
    events = read_events(str(tmp_path / "serve" / "telemetry.jsonl"))
    spans = [s for e in events if e["event"] == "spans" for s in e["spans"]]
    (carried,) = [s for s in spans if s["name"] == "stall"]
    assert (carried["uid"], carried["kind"], carried["depth"]) == (uid, "decode:device_wait", 0)
    assert carried["dur_s"] >= 0.5
    assert {"uid": uid, "kind": "admit"}.items() <= [s for s in spans if s["name"] == "device_dry"
                                                     and s["uid"] == uid][0].items()
    assert {"launch", "account"} <= {s["name"] for s in spans}


def test_a_ticks_typical_length_is_kept_by_the_rows_its_program_ran(served):
    """A tick lasts as long as the program it reads: a whole-shape prefill tick is no stalled
    rung tick (reason-sat on the chip counted two such, PR 53)."""
    sched, _, _, counters = served
    like = {key[1:] for key in trace.recorder()._typical if key[0] == sched._source}
    assert {("tick", "prefill", 1), ("tick", "prefill", SLOTS), ("tick", "decode", SLOTS)} <= like
    assert ("tick", "idle", None) not in like and all(kind != "idle" for _, kind, _ in like)
    assert counters["units_stalled_tick"] == 0
