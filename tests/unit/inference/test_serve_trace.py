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
