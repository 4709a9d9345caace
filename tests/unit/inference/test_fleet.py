"""graft-fleet tier-1 gates (ISSUE 17): the multi-replica router,
autoscaler, and live KV migration under a SIMULATED clock — LocalReplica
replays the worker's signal paths as method calls, so the migrate/readmit
contracts (zero dropped, at-most-once delivery, greedy parity, digest
verification) are proven with zero subprocesses. The real-pipes twin
(SubprocessReplica + fleet/worker.py) runs under @pytest.mark.slow."""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu.elasticity import heartbeat_age
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.fleet import (AutoscalePolicy, Autoscaler,
                                           FleetRouter, LocalReplica,
                                           load_bundle, save_bundle)
from deepspeed_tpu.inference.fleet import protocol
from deepspeed_tpu.inference.fleet.migrate import bundle_rids
from deepspeed_tpu.inference.serving import (REFUSED, BlockPool,
                                             ContinuousBatchingScheduler,
                                             MigrationError, Request,
                                             RequestQueue, ServingConfig,
                                             SERVE_EVENT_SCHEMAS,
                                             iter_serve_events,
                                             last_tick_signals,
                                             validate_event)
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


class SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt: float = 1.0):
        self.t += dt


@pytest.fixture(scope="module")
def engine_cfg():
    set_topology(None)
    cfg = get_gpt2_config("test", n_layer=2, n_positions=128)
    icfg = DeepSpeedInferenceConfig(replace_with_kernel_inject=False)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    engine = InferenceEngine(GPT2LMHeadModel(cfg), icfg, topology=topo)
    yield engine, cfg
    set_topology(None)


def _mk_sched(engine, clock=None, telemetry=None, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousBatchingScheduler(engine, ServingConfig(**kw),
                                       clock=clock, telemetry=telemetry)


def _prompts(cfg, n, length=10, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (length,)).astype(np.int32)
            for _ in range(n)]


def _reference_outputs(engine, prompts, max_new):
    sched = _mk_sched(engine)
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained()
    return [list(r.output) for r in reqs]


# ---------------------------------------------------------------------------
# wire protocol: torn/noise lines never crash the router
# ---------------------------------------------------------------------------

def test_protocol_roundtrip_and_noise():
    msg = protocol.request_msg("r7", np.arange(4, dtype=np.int32), 8, None)
    back = protocol.parse_line(protocol.encode(msg).strip())
    assert back["type"] == "request" and back["rid"] == "r7"
    assert back["prompt"] == [0, 1, 2, 3] and back["max_new_tokens"] == 8
    # noise on the stream — an XLA warning, a torn tail, an empty line —
    # is skipped, never raised to the router
    assert protocol.parse_line("") is None
    assert protocol.parse_line("W0000 gemm autotune fallback") is None
    assert protocol.parse_line('{"type": "tick", "sig') is None
    assert protocol.parse_line('[1, 2, 3]') is None  # JSON but not a message
    with pytest.raises(ValueError):
        protocol.encode({"rid": "no-type"})


# ---------------------------------------------------------------------------
# router accounting on stub replicas (no engine): least-loaded dispatch,
# at-most-once delivery, bounded refusal retries, death re-admission
# ---------------------------------------------------------------------------

class StubReplica:
    def __init__(self, load=0.0, refuse=False):
        self._load = load
        self.refuse = refuse
        self.dead = False
        self.inbox = []
        self.outbox = []

    @property
    def alive(self):
        return not self.dead

    def load(self):
        return float("inf") if self.dead else self._load

    def send(self, msg):
        if self.dead:
            raise RuntimeError("dead")
        self.inbox.append(msg)
        if msg["type"] == "request":
            if self.refuse:
                self.outbox.append({"type": "refused", "rid": msg["rid"],
                                    "reason": "stub refuses everything"})
            else:
                self._load += 1

    def poll(self):
        out, self.outbox = self.outbox, []
        return out

    def finish(self, rid, output=(1, 2)):
        self._load = max(0.0, self._load - 1)
        self.outbox.append({"type": "done", "rid": rid,
                            "output": list(output), "stats": {}})


def test_router_least_loaded_dispatch_and_dedupe():
    router = FleetRouter()
    busy, idle = StubReplica(load=3.0), StubReplica(load=0.0)
    router.add_replica("busy", busy)
    router.add_replica("idle", idle)
    rid = router.submit(np.arange(3, dtype=np.int32), 4)
    assert router.pending[rid]["replica"] == "idle"  # least loaded wins
    assert not busy.inbox and len(idle.inbox) == 1
    # first done wins; a duplicate (migration ack raced a death) is
    # counted, never double-delivered
    idle.finish(rid, output=(9, 9))
    router.poll()
    assert router.completed[rid]["output"] == [9, 9]
    busy.outbox.append({"type": "done", "rid": rid, "output": [0], "stats": {}})
    router.poll()
    assert router.completed[rid]["output"] == [9, 9]  # first delivery kept
    assert router.duplicate_completions == 1
    assert router.stats()["pending"] == 0 and router.stats()["failed"] == 0


def test_router_universal_refusal_is_terminal_not_livelock():
    router = FleetRouter()
    router.add_replica("a", StubReplica(refuse=True))
    router.add_replica("b", StubReplica(refuse=True))
    rid = router.submit(np.arange(3, dtype=np.int32), 4)
    for _ in range(20):  # bounded retries: must converge, not ping-pong
        router.poll()
        if rid in router.failed:
            break
    assert rid in router.failed and rid not in router.pending


def test_router_death_readmits_orphans_on_peer():
    router = FleetRouter()
    doomed, survivor = StubReplica(load=0.0), StubReplica(load=5.0)
    router.add_replica("doomed", doomed)
    router.add_replica("survivor", survivor)
    rid = router.submit(np.arange(3, dtype=np.int32), 4)
    assert router.pending[rid]["replica"] == "doomed"
    doomed.dead = True          # SIGKILL: no drain, no messages
    router.poll()               # liveness sweep
    assert router.pending[rid]["replica"] == "survivor"
    assert router.readmitted == 1
    assert "doomed" not in router.replicas
    survivor.finish(rid)
    router.poll()
    assert rid in router.completed and router.stats()["pending"] == 0


def test_router_heartbeat_staleness_counts_as_death():
    """A replica that still has a live process but a stale heartbeat is
    hung (stuck dispatch) — the router must treat it as dead."""
    router = FleetRouter(heartbeat_timeout=5.0)
    hung = StubReplica()
    hung.heartbeat_age = lambda: 60.0  # way past the timeout
    fresh = StubReplica(load=2.0)
    fresh.heartbeat_age = lambda: 0.1
    router.add_replica("hung", hung)
    router.add_replica("fresh", fresh)
    assert list(router.alive_replicas()) == ["fresh"]
    rid = router.submit(np.arange(3, dtype=np.int32), 4)
    assert router.pending[rid]["replica"] == "fresh"


# ---------------------------------------------------------------------------
# live KV migration: SIGTERM parity, SIGKILL re-admission (LocalReplica)
# ---------------------------------------------------------------------------

def test_sigterm_migrates_inflight_greedy_parity(engine_cfg, tmp_path):
    """SIGTERM one of two replicas mid-flight: every in-flight request's
    KV moves to the peer and every continuation is bit-identical to an
    uninterrupted run — zero dropped, zero duplicates."""
    engine, cfg = engine_cfg
    prompts = _prompts(cfg, 6)
    ref = _reference_outputs(engine, prompts, max_new=6)
    router = FleetRouter()
    r0 = LocalReplica("r0", _mk_sched(engine, kv_quant=True))
    r1 = LocalReplica("r1", _mk_sched(engine, kv_quant=True))
    router.add_replica("r0", r0)
    router.add_replica("r1", r1)
    rids = [router.submit(p, 6) for p in prompts]
    for _ in range(3):
        router.step()
    assert len(r0.scheduler.in_flight) >= 1  # the SIGTERM lands mid-flight
    r0.sigterm(str(tmp_path / "bundle"))
    router.run_until_complete(max_rounds=2000)
    st = router.stats()
    assert st["completed"] == len(prompts), st
    assert st["pending"] == 0 and st["failed"] == 0, st
    assert st["duplicate_completions"] == 0, st
    for i, rid in enumerate(rids):
        assert router.completed[rid]["output"] == ref[i], i
    # the receiving side tagged restored requests with their origin
    migrated = [r for r in r1.scheduler.finished if "migrated_from" in r.meta]
    assert migrated, "nothing actually migrated"


def test_sigkill_readmits_with_at_most_once(engine_cfg):
    """Hard death: no drain, no bundle. The router's sweep re-admits the
    orphans on the survivor; outputs still match the uninterrupted run."""
    engine, cfg = engine_cfg
    prompts = _prompts(cfg, 6)
    ref = _reference_outputs(engine, prompts, max_new=6)
    router = FleetRouter()
    k0 = LocalReplica("k0", _mk_sched(engine))
    k1 = LocalReplica("k1", _mk_sched(engine))
    router.add_replica("k0", k0)
    router.add_replica("k1", k1)
    rids = [router.submit(p, 6) for p in prompts]
    for _ in range(2):
        router.step()
    victim = k0 if k0.scheduler.in_flight else k1
    victim.sigkill()
    router.run_until_complete(max_rounds=2000)
    st = router.stats()
    assert st["completed"] == len(prompts), st
    assert st["failed"] == 0 and st["readmitted"] >= 1, st
    for i, rid in enumerate(rids):
        assert router.completed[rid]["output"] == ref[i], i


def test_sigterm_with_no_peer_falls_back_to_drain(engine_cfg, tmp_path):
    """A single-replica fleet has nowhere to migrate: the SIGTERM path
    still publishes the bundle, and the router (no alive peer) keeps the
    rids pending until a replica appears — nothing is dropped."""
    engine, cfg = engine_cfg
    prompts = _prompts(cfg, 2)
    ref = _reference_outputs(engine, prompts, max_new=6)
    router = FleetRouter()
    solo = LocalReplica("solo", _mk_sched(engine))
    router.add_replica("solo", solo)
    rids = [router.submit(p, 6) for p in prompts]
    for _ in range(2):
        router.step()
    solo.sigterm(str(tmp_path / "bundle"))
    router.poll()  # migrated_out lands with no peer; death sweep runs
    assert all(rid in router.pending for rid in rids
               if rid not in router.completed)
    # a late-arriving replica picks the work back up (re-run from prompt
    # or bundle re-admission — either way, zero dropped)
    late = LocalReplica("late", _mk_sched(engine))
    router.add_replica("late", late)
    for rid in list(router.pending):
        if router.pending[rid]["replica"] is None:
            router.dispatch(rid)
    router.run_until_complete(max_rounds=2000)
    st = router.stats()
    assert st["completed"] == len(prompts) and st["failed"] == 0, st
    for i, rid in enumerate(rids):
        assert router.completed[rid]["output"] == ref[i], i


# ---------------------------------------------------------------------------
# migration codec: digest verification, compat vs capacity refusals
# ---------------------------------------------------------------------------

def _midflight_sched(engine, cfg, n=2, **kw):
    sched = _mk_sched(engine, **kw)
    for p in _prompts(cfg, n, seed=23):
        sched.submit(Request(prompt=p, max_new_tokens=6))
    for _ in range(3):
        sched.step()
    assert sched.in_flight
    return sched


def test_bundle_corruption_is_loud(engine_cfg, tmp_path):
    """A migration bundle is a PR-9 manifest checkpoint: a flipped byte in
    any npz must fail the digest verify (MigrationError), never restore
    silently-wrong KV."""
    engine, cfg = engine_cfg
    sched = _midflight_sched(engine, cfg)
    payloads = sched.export_inflight(release=False)
    bundle = str(tmp_path / "bundle")
    save_bundle(payloads, bundle)
    sched.release_inflight()
    # intact bundle round-trips with the same rids
    assert bundle_rids(load_bundle(bundle)) == bundle_rids(payloads)
    victim = next(f for f in sorted(os.listdir(bundle)) if f.endswith(".npz"))
    path = os.path.join(bundle, victim)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    with pytest.raises(MigrationError):
        load_bundle(bundle)


def test_sampling_refuses_migration(engine_cfg):
    """do_sample serving cannot migrate (the rng stream is scheduler-
    global): export must refuse loudly BEFORE releasing any slot, so the
    drain fallback still owns the requests."""
    engine, cfg = engine_cfg
    sched = _midflight_sched(engine, cfg, do_sample=True, temperature=0.8)
    inflight = len(sched.in_flight)
    with pytest.raises(MigrationError, match="sampled decoding"):
        sched.export_inflight()
    assert len(sched.in_flight) == inflight  # untouched: drainable
    sched.run_until_drained()
    assert not sched.in_flight


def test_compat_mismatch_refuses_capacity_shortfall_returns_none(engine_cfg):
    """The two refusal classes stay distinct: a kv_quant mismatch is a
    compat error no retry fixes (MigrationError); a full replica is a
    capacity shortfall (None) the router retries elsewhere."""
    engine, cfg = engine_cfg
    src = _midflight_sched(engine, cfg, kv_quant=True)
    payloads = src.export_inflight(release=False)
    fp_receiver = _mk_sched(engine, kv_quant=False)
    with pytest.raises(MigrationError, match="kv_quant"):
        fp_receiver.admit_migrated(payloads[0])
    # saturate a compatible receiver: every slot busy -> capacity None
    full = _midflight_sched(engine, cfg, n=4, kv_quant=True)
    assert len(full.in_flight) == 4
    assert full.admit_migrated(payloads[0]) is None
    src.release_inflight()
    full.run_until_drained()


# ---------------------------------------------------------------------------
# autoscaler: thresholds + hysteresis, offline replay from telemetry
# ---------------------------------------------------------------------------

def _sig(queue=0, in_flight=0, slots=4, ttft=None, frag=0):
    return {"queue_depth": queue, "in_flight": in_flight, "slots": slots,
            "ttft_p99": ttft, "pool_fragmentation_tokens": frag}


def test_autoscaler_thresholds_and_hysteresis():
    clock = SimClock()
    a = Autoscaler(AutoscalePolicy(max_replicas=3, queue_high=2.0,
                                   scale_up_cooldown_s=10.0,
                                   scale_down_cooldown_s=10.0,
                                   flap_guard_s=5.0), clock=clock)
    assert a.decide({}) == 0 and a.last_reason == "no signals yet"
    hot = {"a": _sig(queue=5, in_flight=4)}
    assert a.decide(hot) == +1
    assert a.decide(hot) == 0            # up-cooldown holds
    clock.advance(11.0)
    assert a.decide(hot) == +1
    clock.advance(2.0)
    cold = {"a": _sig(), "b": _sig()}
    assert a.decide(cold) == 0           # flap guard: an up just fired
    assert "cooldown" in a.last_reason
    clock.advance(20.0)
    assert a.decide(cold) == -1
    # survivors must absorb in-flight load before a scale-down: occupancy
    # reads idle (6/8 < 0.9) but one replica's 4 slots cannot hold 6
    absorb = Autoscaler(AutoscalePolicy(occupancy_low=0.9,
                                        scale_down_cooldown_s=0.0,
                                        flap_guard_s=0.0), clock=SimClock())
    busy_idle = {"a": _sig(in_flight=3), "b": _sig(in_flight=3)}
    assert absorb.decide(busy_idle, now=1.0) == 0
    assert "absorb" in absorb.last_reason
    # min/max clamps
    clock.advance(20.0)
    assert a.decide({"a": _sig()}) == 0  # already at min_replicas
    full = {n: _sig(queue=9) for n in "abc"}
    assert a.decide(full) == 0 and "max_replicas" in a.last_reason
    assert [d["delta"] for d in a.decisions] == [+1, +1, -1]


def test_autoscaler_latency_and_fragmentation_triggers():
    a = Autoscaler(AutoscalePolicy(ttft_p99_high=0.5, frag_tokens_high=100,
                                   scale_up_cooldown_s=0.0, flap_guard_s=0.0),
                   clock=SimClock())
    assert a.decide({"a": _sig(ttft=0.9)}, now=1.0) == +1
    assert "ttft_p99" in a.last_reason
    a2 = Autoscaler(AutoscalePolicy(frag_tokens_high=100,
                                    scale_up_cooldown_s=0.0, flap_guard_s=0.0),
                    clock=SimClock())
    assert a2.decide({"a": _sig(frag=500)}, now=1.0) == +1
    assert "frag" in a2.last_reason


def test_autoscaler_offline_replay_from_telemetry(tmp_path):
    """A decision is reproducible from the run directories alone: the
    file-tailing deployment (no pipes) reads each replica's newest
    serve_tick and decides identically."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import TELEMETRY_FILE, RuntimeTelemetry
    paths = {}
    for name, queue in (("hot", 8), ("warm", 6)):
        t = RuntimeTelemetry(TelemetryConfig(enabled=True,
                                             output_path=str(tmp_path),
                                             job_name=name))
        t.write_run_header({"bench": "test"})
        # an older tick then a newer one: the replay must use the newest
        t.emit("serve_tick", tick=1, kind="decode", **_sig(queue=0),
               free_slots=4, ttft_p50=None)
        t.emit("serve_tick", tick=2, kind="decode", **_sig(queue=queue,
                                                           in_flight=4),
               free_slots=0, ttft_p50=None)
        t.close()
        paths[name] = os.path.join(t.run_dir, TELEMETRY_FILE)
    sigs = Autoscaler.signals_from_telemetry(paths)
    assert sigs["hot"]["queue_depth"] == 8 and sigs["warm"]["queue_depth"] == 6
    a = Autoscaler(AutoscalePolicy(queue_high=4.0, scale_up_cooldown_s=0.0,
                                   flap_guard_s=0.0), clock=SimClock())
    assert a.decide(sigs, now=1.0) == +1


# ---------------------------------------------------------------------------
# satellite 3: refuse_all terminal accounting + serving event schemas
# ---------------------------------------------------------------------------

def test_refuse_all_terminal_state_accounting():
    """Every queued request refuse_all drains must land TERMINAL: state
    REFUSED, a human-readable reason, the queue's refused counter
    matching, and zero pool blocks touched (nothing was ever admitted)."""
    pool = BlockPool(num_blocks=16, block_size=16)
    q = RequestQueue(pool, max_total_tokens=256)
    reqs = [Request(prompt=np.arange(8, dtype=np.int32), max_new_tokens=4)
            for _ in range(3)]
    for r in reqs:
        q.submit(r)
    assert len(q) == 3 and q.refused == 0
    refused = q.refuse_all("draining on SIGTERM")
    assert [r.request_id for r in refused] == [r.request_id for r in reqs]
    assert all(r.state == REFUSED for r in reqs)
    assert all(r.refuse_reason == "draining on SIGTERM" for r in reqs)
    assert all(r.done for r in reqs)          # terminal, not re-queued
    assert len(q) == 0 and q.refused == 3 and q.submitted == 3
    assert pool.used_blocks == 0              # nothing reserved, nothing leaked
    assert q.refuse_all("again") == []        # idempotent on empty


def test_serve_event_schema_validation():
    ok = {"event": "serve_drain", "signal": "SIGTERM", "in_flight": 2,
          "refused": 3}
    validate_event(ok)
    with pytest.raises(ValueError, match="refused"):
        validate_event({"event": "serve_drain", "signal": "SIGTERM",
                        "in_flight": 2})
    validate_event({"event": "not_a_serving_event"})  # foreign kinds pass
    # every documented kind has a non-empty field set
    assert set(SERVE_EVENT_SCHEMAS) >= {"serve_tick", "serve_drain",
                                        "serve_migrate_out",
                                        "serve_migrate_in",
                                        "serve_admit_migrated"}
    assert all(SERVE_EVENT_SCHEMAS[k] for k in SERVE_EVENT_SCHEMAS)


def test_serve_tick_and_drain_events_land_schema_valid(engine_cfg, tmp_path):
    """Satellite 1 end-to-end: a served-then-preempted scheduler lands
    serve_tick AND serve_drain JSONL that validates against the schema,
    and last_tick_signals reads back the newest tick."""
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import TELEMETRY_FILE, RuntimeTelemetry
    engine, cfg = engine_cfg
    telem = RuntimeTelemetry(TelemetryConfig(enabled=True,
                                             output_path=str(tmp_path),
                                             job_name="fleet_test"))
    telem.write_run_header({"bench": "test"})
    sched = _mk_sched(engine, telemetry=telem, tick_telemetry_every=1)

    class FakeGuard:
        requested = False
        installed = True

        def consume(self):
            return "SIGTERM"

    guard = FakeGuard()
    reqs = [Request(prompt=p, max_new_tokens=6)
            for p in _prompts(cfg, 4, seed=7)]
    for r in reqs[:2]:
        sched.submit(r)
    sched.step()
    guard.requested = True  # preempt mid-flight with 2 still queued
    for r in reqs[2:]:
        sched.submit(r)
    rc = sched.serve(guard=guard)
    assert rc == 143
    telem.close()
    path = os.path.join(telem.run_dir, TELEMETRY_FILE)
    ticks = list(iter_serve_events(path, kinds=("serve_tick",)))
    assert ticks, "no serve_tick events landed"
    for rec in ticks:
        validate_event(rec)
    drains = list(iter_serve_events(path, kinds=("serve_drain",)))
    assert len(drains) == 1
    validate_event(drains[0])
    assert drains[0]["refused"] == 2 and drains[0]["signal"] == "SIGTERM"
    last = last_tick_signals(path)
    assert last["tick"] == max(r["tick"] for r in ticks)
    # per-request retirement rows rode along, schema-valid
    for rec in iter_serve_events(path, kinds=("serve_request",)):
        validate_event(rec)


def test_tick_telemetry_cadence_zero_disables(engine_cfg, tmp_path):
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import TELEMETRY_FILE, RuntimeTelemetry
    engine, cfg = engine_cfg
    telem = RuntimeTelemetry(TelemetryConfig(enabled=True,
                                             output_path=str(tmp_path),
                                             job_name="quiet"))
    telem.write_run_header({"bench": "test"})
    sched = _mk_sched(engine, telemetry=telem, tick_telemetry_every=0)
    sched.submit(Request(prompt=_prompts(cfg, 1)[0], max_new_tokens=4))
    sched.run_until_drained()
    telem.close()
    path = os.path.join(telem.run_dir, TELEMETRY_FILE)
    assert not list(iter_serve_events(path, kinds=("serve_tick",)))


# ---------------------------------------------------------------------------
# satellite 2: heartbeat staleness helper + serving role payload
# ---------------------------------------------------------------------------

def test_heartbeat_age_staleness(tmp_path):
    assert heartbeat_age(None) is None              # unsupervised: no signal
    missing = str(tmp_path / "nope")
    assert heartbeat_age(missing) is None           # never written yet
    hb = str(tmp_path / "hb")
    open(hb, "w").close()
    os.utime(hb, (0, 0))
    age = heartbeat_age(hb, now=time.time())
    assert age is not None and age > 1e6            # ancient file: very stale
    os.utime(hb, None)
    assert heartbeat_age(hb) < 5.0                  # fresh touch: near zero
    # clock skew (mtime in the future) clamps to 0, never negative
    os.utime(hb, (time.time() + 100, time.time() + 100))
    assert heartbeat_age(hb) == 0.0


def test_scheduler_heartbeat_carries_serving_role(engine_cfg, tmp_path,
                                                  monkeypatch):
    from deepspeed_tpu.elasticity.elastic_agent import read_heartbeat
    engine, cfg = engine_cfg
    hb = str(tmp_path / "hb")
    monkeypatch.setenv("DS_ELASTIC_HEARTBEAT_FILE", hb)
    sched = _mk_sched(engine, heartbeat_interval=0.0)
    sched.submit(Request(prompt=_prompts(cfg, 1)[0], max_new_tokens=4))
    sched.run_until_drained()
    data = read_heartbeat(hb)
    assert data["role"] == "serving"
    assert data["pid"] == os.getpid()
    assert {"tick", "slots_in_flight", "queue_depth",
            "last_tick_monotonic"} <= set(data)


# ---------------------------------------------------------------------------
# real pipes: SubprocessReplica + fleet/worker.py (excluded from tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_subprocess_fleet_smoke(tmp_path):
    """Two real worker processes behind the router: requests complete
    over the pipes, a real SIGTERM migrates in-flight work to the peer,
    and nothing is dropped."""
    from deepspeed_tpu.inference.fleet import SubprocessReplica
    env = {"JAX_PLATFORMS": "cpu", "FLEET_MODEL": "test",
           "FLEET_POSITIONS": "128", "FLEET_SLOTS": "4", "FLEET_CHUNK": "8",
           "FLEET_TELEMETRY_DIR": str(tmp_path / "telemetry")}
    router = FleetRouter(heartbeat_timeout=120.0)
    replicas = [SubprocessReplica(f"w{i}", str(tmp_path / f"w{i}"), env=env)
                for i in range(2)]
    try:
        for r in replicas:
            r.wait_ready(timeout=300.0)
            router.add_replica(r.name, r)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 50257, (10,)).astype(np.int32)
                   for _ in range(6)]
        rids = [router.submit(p, 6) for p in prompts]
        deadline = time.monotonic() + 300.0
        termed = False
        while router.pending and time.monotonic() < deadline:
            router.poll()
            # exactly ONE real SIGTERM once w0 reports work in flight (a
            # second signal would escalate the guard to a hard exit)
            sig = replicas[0].signals()
            if (not termed and replicas[0].alive and sig
                    and sig.get("in_flight", 0) > 0):
                replicas[0].sigterm()
                termed = True
            time.sleep(0.02)
        st = router.stats()
        assert st["completed"] == len(prompts), (st, router.failed)
        assert st["failed"] == 0, router.failed
        assert all(rid in router.completed for rid in rids)
        assert termed, "w0 never reported work in flight"
        # the worker exits 143 *after* announcing migrated_out/bye — give
        # the process a moment to actually leave
        assert replicas[0].wait(60.0) == 143
    finally:
        for r in replicas:
            r.close()
