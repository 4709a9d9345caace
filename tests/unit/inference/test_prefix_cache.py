"""graft-prefix-cache tier-1 gates (ISSUE 19): the content-addressed
ref-counted BlockPool — chain-hash matching, copy-on-write partials,
cached-free LRU eviction, loud double-free refusal, randomized-stream
invariants — plus the scheduler-level contracts riding on it: exact
greedy parity cache-on vs cache-off with prefill-skip evidence, the
serve_tick/serve_request schema fields, digest-verified migration of a
request holding SHARED prefix blocks, router prefix-affinity dispatch,
and the decode-program byte-identity pin (the cache is host-side
accounting only — it must never change the compiled step)."""

import os

import numpy as np
import pytest

import jax

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.fleet import FleetRouter, load_bundle, save_bundle
from deepspeed_tpu.inference.serving import (BlockPool,
                                             ContinuousBatchingScheduler,
                                             FINISHED,
                                             MigrationError, Request,
                                             ServingConfig,
                                             iter_serve_events,
                                             validate_event)
from deepspeed_tpu.inference.serving.blocks import _ROOT, chain_hash, prefix_key
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


def _fetch_for(tokens):
    """Opaque pool-level publish payload: the pool never interprets it,
    it only hands it back on a match."""
    toks = [int(t) for t in tokens]
    return lambda start, stop: {"blk": tuple(toks[start:stop])}


# ---------------------------------------------------------------------------
# pool: content-addressed sharing (property: same tokens -> same block)
# ---------------------------------------------------------------------------

def test_same_prompt_attaches_same_blocks_by_reference():
    pool = BlockPool(16, 4, prefix_cache=True)
    p = list(range(100, 112))  # 3 full blocks
    pool.reserve(1, 18, prompt=p)
    pool.publish(1, p, fetch=_fetch_for(p))
    t1 = pool.block_table(1)
    pool.reserve(2, 18, prompt=p)
    t2 = pool.block_table(2)
    # full-block matches attach the SAME physical blocks by reference;
    # the last block is always copy-on-write (>= 1 token stays uncached)
    assert t2[:2] == t1[:2]
    assert t2[2] != t1[2]
    assert pool._refs[t1[0]] == 2 and pool._refs[t1[1]] == 2
    m = pool.take_match(2)
    assert m.cached_tokens == 11 and len(m.full_hashes) == 2
    assert m.partial_tokens == 3  # block-aligned prompt: bs-1 rows COW'd
    assert pool.seq_len(2) == 11  # prefill restarts after the cached prefix
    assert pool.cached_tokens_served == 11
    # the chain key is deterministic and envelope-sensitive
    assert chain_hash(_ROOT, p[:4]) == chain_hash(_ROOT, p[:4])
    assert chain_hash(_ROOT, p[:4], "kvq:1") != chain_hash(_ROOT, p[:4])
    pool.free(1)
    pool.free(2)


def test_match_stops_at_first_differing_token():
    pool = BlockPool(16, 4, prefix_cache=True)
    p = list(range(100, 112))
    pool.reserve(1, 12, prompt=p)
    pool.publish(1, p, fetch=_fetch_for(p))
    q = list(p)
    q[5] = 999  # diverges inside block 1
    m = pool.match_prefix(q)
    assert m.cached_tokens == 5  # exactly the divergence index
    assert len(m.full_hashes) == 1 and m.partial_tokens == 1
    q0 = list(p)
    q0[0] = 999  # diverges at position 0: nothing reusable
    assert pool.match_prefix(q0).cached_tokens == 0
    pool.reserve(2, 12, prompt=q0)
    # two misses total: seq 1 reserved against an empty index, seq 2
    # diverged at position 0
    assert pool.prefix_misses == 2 and pool.take_match(2) is None


def test_blocks_published_without_payload_are_unmatchable():
    # no bytes to restore => a hash hit would be silent corruption
    pool = BlockPool(8, 4, prefix_cache=True)
    p = list(range(8))
    pool.reserve(1, 8, prompt=p)
    pool.publish(1, p)  # fetch=None: indexed, payloadless
    assert pool.match_prefix(p).cached_tokens == 0
    pool.reserve(2, 8, prompt=p)
    assert pool.prefix_hits == 0 and pool.prefix_misses == 2


# ---------------------------------------------------------------------------
# pool: loud-refusal free semantics (satellite 2)
# ---------------------------------------------------------------------------

def test_free_unknown_or_double_free_is_loud():
    pool = BlockPool(4, 4, prefix_cache=True)
    with pytest.raises(KeyError, match="unknown or already-freed"):
        pool.free(7)
    pool.reserve(1, 4)
    pool.free(1)
    with pytest.raises(KeyError, match="double-free"):
        pool.free(1)
    # double-allocate of a live id is equally loud
    pool.reserve(2, 4)
    with pytest.raises(KeyError, match="already"):
        pool.allocate(2)
    pool.free(2)
    assert pool.free_blocks == pool.num_blocks
    assert pool.total_allocs == pool.total_frees == 2


# ---------------------------------------------------------------------------
# pool: eviction reclaims only ref-0 cached blocks, never live refs
# ---------------------------------------------------------------------------

def test_eviction_never_frees_blocks_with_live_refs():
    pool = BlockPool(4, 4, prefix_cache=True)
    p = list(range(16))
    pool.reserve(1, 16, prompt=p)
    pool.publish(1, p, fetch=_fetch_for(p))
    pool.free(1)
    assert pool.cached_blocks == 4 and pool.free_blocks == 4
    # an unrelated reservation must evict the cached-free LRU blocks
    q = [7000 + i for i in range(16)]
    pool.reserve(2, 16, prompt=q)
    t2 = pool.block_table(2)
    assert pool.prefix_evictions == 4 and pool.cached_blocks == 0
    # every block now holds a live ref: exhaustion refuses loudly instead
    # of stealing one
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.reserve(3, 4)
    # the failed reservation rolled back completely (seq 3 not live)
    with pytest.raises(KeyError):
        pool.free(3)
    assert all(pool._refs[b] == 1 for b in t2)
    pool.free(2)
    assert pool.free_blocks == pool.num_blocks


def test_revive_off_lru_then_evict_under_pressure():
    pool = BlockPool(4, 4, prefix_cache=True)
    p = list(range(16))
    pool.reserve(1, 16, prompt=p)
    pool.publish(1, p, fetch=_fetch_for(p))
    t1 = pool.block_table(1)
    pool.free(1)
    # same prompt again: the three matched full blocks revive off the LRU
    # (same physical blocks, zero evictions for them); the COW partial
    # evicts the one remaining cached-free block
    pool.reserve(2, 16, prompt=p)
    t2 = pool.block_table(2)
    assert t2[:3] == t1[:3]
    assert pool.cached_blocks == 0 and pool.prefix_evictions == 1
    assert pool.used_blocks == 4
    pool.free(2)


# ---------------------------------------------------------------------------
# pool: copy-on-write partial match never mutates the shared source
# ---------------------------------------------------------------------------

def test_cow_partial_match_shares_payload_but_charges_fresh_block():
    pool = BlockPool(8, 4, prefix_cache=True)
    p = list(range(8))
    payloads = {}

    def fetch(start, stop):
        arr = np.arange(start, stop, dtype=np.int32)
        payloads[(start, stop)] = arr
        return arr

    pool.reserve(1, 8, prompt=p)
    pool.publish(1, p, fetch=fetch)
    q = p[:6] + [777, 778]
    pool.reserve(2, 10, prompt=q)
    m = pool.take_match(2)
    assert m.cached_tokens == 6 and m.partial_tokens == 2
    # the partial payload is the SOURCE block's payload object, shared
    # zero-copy — the consumer reads its first partial_tokens rows
    assert m.partial_payload is payloads[(4, 8)]
    # COW: the shared source block is never attached to seq 2
    src = pool._block_of[chain_hash(chain_hash(_ROOT, p[:4]), p[4:8])]
    assert src not in pool.block_table(2)
    assert pool._refs[src] == 1  # still only seq 1's reference
    # and the source payload bytes are untouched
    assert np.array_equal(payloads[(4, 8)], np.arange(4, 8, dtype=np.int32))
    pool.free(1)
    pool.free(2)


# ---------------------------------------------------------------------------
# pool: publish dedup + the concurrent-prefill race
# ---------------------------------------------------------------------------

def test_publish_dedup_and_race_keeps_first_copy_canonical():
    pool = BlockPool(8, 4, prefix_cache=True)
    p = list(range(8))
    calls = []

    def fetch(start, stop):
        calls.append((start, stop))
        return {"blk": tuple(p[start:stop])}

    pool.reserve(1, 8)  # no prompt: private blocks (both admitted pre-index)
    pool.reserve(2, 8)
    assert pool.publish(1, p, fetch=fetch) == 2
    assert calls == [(0, 4), (4, 8)]
    # re-publishing the same sequence is free: blocks already hashed
    assert pool.publish(1, p, fetch=fetch) == 0
    # seq 2 raced with identical content in different blocks: the first
    # copy stays canonical, seq 2's blocks stay private
    assert pool.publish(2, p, fetch=fetch) == 0
    assert len(calls) == 2 and pool.published_blocks == 2
    assert all(b not in pool._hash_of for b in pool.block_table(2))
    pool.free(1)
    pool.free(2)
    # seq 1's hashed blocks parked on the LRU, seq 2's returned plain free
    assert pool.cached_blocks == 2 and pool.free_blocks == 8


def test_hot_prefixes_and_hit_rate():
    pool = BlockPool(8, 4, prefix_cache=True)
    assert pool.prefix_hit_rate() is None
    p = list(range(8))
    pool.reserve(1, 8, prompt=p)  # miss: index empty
    pool.publish(1, p, fetch=_fetch_for(p))
    pool.reserve(2, 8, prompt=p)  # hit
    assert pool.prefix_hits == 1 and pool.prefix_misses == 1
    c = pool.counters()
    assert c["prefix_hit_rate"] == 0.5
    assert c["published_blocks"] == 2
    # the advertised hot set is the envelope-free key of position-0 blocks
    assert pool.hot_prefixes() == [prefix_key(p[:4])]
    pool.free(1)
    pool.free(2)


def test_can_allocate_discounts_only_in_use_shared_blocks():
    pool = BlockPool(4, 4, prefix_cache=True)
    p = list(range(12))
    pool.reserve(1, 12, prompt=p)
    pool.publish(1, p, fetch=_fetch_for(p))
    # worst case 3 blocks > 1 free — but two full blocks attach by
    # reference to seq 1's live copies, so the same-prefix prompt fits
    assert not pool.can_allocate(12)
    assert pool.can_allocate(12, prompt=p)
    pool.reserve(2, 12, prompt=p)  # proves the probe told the truth
    pool.free(1)
    pool.free(2)
    # all matched blocks cached-free now: reviving consumes them from the
    # reclaimable pool, so they are NOT discounted (but they still fit)
    assert pool.can_allocate(12, prompt=p) and pool.can_allocate(16)
    assert not pool.can_allocate(17)


# ---------------------------------------------------------------------------
# pool: randomized shared-prefix request streams keep every invariant
# ---------------------------------------------------------------------------

def _check_pool_invariants(pool):
    in_use, ref_count = set(), {}
    for sid in pool.live_sequences():
        for b in pool.block_table(sid):
            in_use.add(b)
            ref_count[b] = ref_count.get(b, 0) + 1
    free, cached = set(pool._free), set(pool._cached.values())
    # every block is in exactly one of: free list, cached-free LRU, a table
    assert not (in_use & free) and not (in_use & cached)
    assert not (free & cached)
    assert len(in_use) + len(free) + len(cached) == pool.num_blocks
    # ref counts agree with table membership exactly
    for b, n in ref_count.items():
        assert pool._refs[b] == n, (b, n, pool._refs[b])
    # cached-free blocks are ref-0 and still indexed (else unmatchable)
    for h, b in pool._cached.items():
        assert pool._block_of[h] == b and b not in pool._refs
    assert pool.used_blocks == len(in_use)
    assert pool.fragmentation_tokens() >= 0


def test_randomized_streams_counter_invariants():
    rng = np.random.default_rng(19)
    pool = BlockPool(24, 4, prefix_cache=True)
    templates = [[int(t) for t in rng.integers(0, 1000, n)] for n in (8, 12)]
    live, next_sid = {}, 0
    for _ in range(400):
        op = int(rng.integers(0, 4))
        if op == 0 or not live:
            t = templates[int(rng.integers(0, len(templates)))]
            suffix = [int(x) for x in rng.integers(0, 1000,
                                                   int(rng.integers(1, 9)))]
            prompt = t + suffix
            total = len(prompt) + int(rng.integers(1, 9))
            sid, next_sid = next_sid, next_sid + 1
            try:
                pool.reserve(sid, total, prompt=prompt)
            except RuntimeError:
                # exhaustion rolls back loudly and completely
                assert sid not in pool.live_sequences()
            else:
                live[sid] = prompt
                pool.take_match(sid)
        elif op == 1:
            sid = int(rng.choice(list(live)))
            pool.publish(sid, live[sid], fetch=_fetch_for(live[sid]))
        elif op == 2:
            sid = int(rng.choice(list(live)))
            try:
                pool.advance(sid, 1)
            except RuntimeError:
                pass  # pool full: table untouched (checked below)
        else:
            sid = int(rng.choice(list(live)))
            pool.free(sid)
            del live[sid]
        _check_pool_invariants(pool)
    for sid in list(live):
        pool.free(sid)
    c = pool.counters()
    assert c["used_blocks"] == 0
    assert c["free_blocks"] == c["num_blocks"]
    assert c["total_allocs"] == c["total_frees"]
    assert pool.prefix_hits > 0 and pool.published_blocks > 0


def test_prefix_cache_off_is_the_private_pool():
    # the paged-KV default: nothing hashes, nothing parks, free is LIFO
    pool = BlockPool(8, 4, prefix_cache=False)
    p = list(range(8))
    pool.reserve(1, 8, prompt=p)
    assert pool.publish(1, p, fetch=_fetch_for(p)) == 0
    assert pool.match_prefix(p).cached_tokens == 0
    pool.free(1)
    assert pool.cached_blocks == 0 and pool.free_blocks == 8
    assert pool.prefix_hits == pool.prefix_misses == 0


# ---------------------------------------------------------------------------
# scheduler: exact greedy parity cache-on vs cache-off + prefill skip
# ---------------------------------------------------------------------------

def _mk_sched(engine, clock=None, telemetry=None, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 16)
    return ContinuousBatchingScheduler(engine, ServingConfig(**kw),
                                       clock=clock, telemetry=telemetry)


def _shared_prefix_prompts(cfg, n, template_len=24, suffix_len=6, seed=11):
    rng = np.random.default_rng(seed)
    template = rng.integers(0, cfg.vocab_size, template_len).astype(np.int32)
    return [np.concatenate([template,
                            rng.integers(0, cfg.vocab_size,
                                         suffix_len).astype(np.int32)])
            for _ in range(n)]


def test_cache_on_greedy_parity_and_prefill_skip(engine_cfg):
    engine, cfg = engine_cfg
    prompts = _shared_prefix_prompts(cfg, 4)

    def run(mode):
        sched = _mk_sched(engine, clock=SimClock(), prefix_cache=mode)
        reqs = []
        for p in prompts:  # sequential: each publishes before the next
            r = Request(prompt=p, max_new_tokens=5)
            sched.submit(r)
            sched.run_until_drained()
            reqs.append(r)
        return reqs, sched

    off_reqs, off_sched = run("off")
    on_reqs, on_sched = run("on")
    assert all(r.state == FINISHED for r in on_reqs)
    # exact greedy parity: restored KV rows ARE the prefilled rows
    assert [r.output for r in on_reqs] == [r.output for r in off_reqs]
    assert all(len(r.output) == 5 for r in on_reqs)
    # prefill-skip evidence: the first request paid full prefill, every
    # later one restored at least the template's full first block
    assert on_reqs[0].cached_prefix_tokens == 0
    assert all(r.cached_prefix_tokens >= 16 for r in on_reqs[1:])
    assert all(r.cached_prefix_tokens == 0 for r in off_reqs)
    assert on_sched.ticks["prefill"] < off_sched.ticks["prefill"]
    # signals carry the router/autoscaler evidence
    sig = on_sched.signals()
    assert sig["prefix_cache_hit_rate"] == 0.75  # 3 hits / 4 prompts
    assert sig["cached_blocks"] >= 1 and sig["prefix_hot"]
    assert off_sched.signals()["prefix_cache_hit_rate"] is None
    stats = on_sched.stats()
    assert stats["prefix_cache"] == "on"
    assert stats["cached_prefix_tokens"] == sum(r.cached_prefix_tokens
                                                for r in on_reqs)
    assert stats["pool"]["prefix_evictions"] == 0  # pool never under pressure


def test_env_knob_and_default_resolution(engine_cfg):
    """``ServingConfig.prefix_cache`` alone decides: on by default, off where
    the configuration says so, anything else refused at the configuration."""
    engine, cfg = engine_cfg
    assert ServingConfig().prefix_cache == "on"
    sched = _mk_sched(engine, clock=SimClock())
    assert sched.prefix_cache == "on" and sched.pool.prefix_cache
    assert sched.stats()["prefix_cache"] == "on"
    sched = _mk_sched(engine, clock=SimClock(), prefix_cache="off")
    assert sched.prefix_cache == "off" and not sched.pool.prefix_cache
    assert sched.stats()["prefix_cache"] == "off"
    for bad in ("sideways", None):
        with pytest.raises(ValueError, match="prefix_cache"):
            ServingConfig(prefix_cache=bad)


# ---------------------------------------------------------------------------
# events: serve_tick / serve_request carry the prefix-cache fields
# ---------------------------------------------------------------------------

def test_serve_events_carry_prefix_fields(engine_cfg, tmp_path):
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.telemetry import TELEMETRY_FILE, RuntimeTelemetry
    engine, cfg = engine_cfg
    telem = RuntimeTelemetry(TelemetryConfig(enabled=True,
                                             output_path=str(tmp_path),
                                             job_name="prefix_test"))
    telem.write_run_header({"bench": "test"})
    sched = _mk_sched(engine, clock=SimClock(), telemetry=telem,
                      tick_telemetry_every=1, prefix_cache="on")
    for p in _shared_prefix_prompts(cfg, 2, seed=5):
        sched.submit(Request(prompt=p, max_new_tokens=4))
        sched.run_until_drained()
    telem.close()
    path = os.path.join(telem.run_dir, TELEMETRY_FILE)
    ticks = list(iter_serve_events(path, kinds=("serve_tick",)))
    assert ticks
    for rec in ticks:
        validate_event(rec)  # schema now REQUIRES the prefix fields
        assert "prefix_cache_hit_rate" in rec and "cached_blocks" in rec
    reqs = list(iter_serve_events(path, kinds=("serve_request",)))
    assert len(reqs) == 2
    for rec in reqs:
        validate_event(rec)
    # the second request's retirement row shows the restored prefix
    assert ticks[-1]["prefix_cache_hit_rate"] == 0.5
    assert max(r["cached_prefix_tokens"] for r in reqs) >= 16
    # a producer dropping the new fields is refused
    bad = {k: v for k, v in ticks[-1].items() if k != "cached_blocks"}
    with pytest.raises(ValueError, match="cached_blocks"):
        validate_event(bad)


# ---------------------------------------------------------------------------
# migration: a request HOLDING shared prefix blocks survives the bundle
# round-trip digest-verified, with greedy parity on the continuation
# ---------------------------------------------------------------------------

def test_migrated_shared_block_request_digest_verified_parity(engine_cfg,
                                                              tmp_path):
    engine, cfg = engine_cfg
    prompts = _shared_prefix_prompts(cfg, 2, seed=23)
    # reference: the second request served uninterrupted, cache off
    ref_sched = _mk_sched(engine, clock=SimClock(), prefix_cache="off")
    ref = Request(prompt=prompts[1], max_new_tokens=6)
    ref_sched.submit(ref)
    ref_sched.run_until_drained()

    src = _mk_sched(engine, clock=SimClock(), prefix_cache="on")
    warm = Request(prompt=prompts[0], max_new_tokens=6)
    src.submit(warm)
    src.run_until_drained()  # publishes the shared template blocks
    req = Request(prompt=prompts[1], max_new_tokens=6)
    src.submit(req)
    src.step()  # admit: attaches the published blocks by reference
    assert req.cached_prefix_tokens >= 16  # proof it holds SHARED blocks
    src.step()  # a little real progress before the migration

    payloads = src.export_inflight(release=False)
    assert len(payloads) == 1 and payloads[0]["prefix_cache"] == "on"
    bundle = save_bundle(payloads, str(tmp_path / "bundle"))
    src.release_inflight()
    loaded = load_bundle(bundle)  # digest-verified read-back

    # compat: a receiver with the cache off refuses loudly (its pool
    # could not re-match or re-publish what this request carries)
    with pytest.raises(MigrationError, match="prefix_cache"):
        _mk_sched(engine, clock=SimClock(),
                  prefix_cache="off").admit_migrated(loaded[0])

    dst = _mk_sched(engine, clock=SimClock(), prefix_cache="on")
    moved = dst.admit_migrated(loaded[0])
    assert moved is not None
    assert moved.meta["migrated_from"] == req.request_id
    assert moved.cached_prefix_tokens == req.cached_prefix_tokens
    dst.run_until_drained()
    # the continuation is bit-identical to the uninterrupted run: the
    # exported KV was materialized per-slot (shared blocks export their
    # bytes, not their refs), so the peer needs no shared state
    assert moved.output == ref.output


# ---------------------------------------------------------------------------
# router: prefix-affinity dispatch (stub replicas, no engine)
# ---------------------------------------------------------------------------

class _AffinityStub:
    def __init__(self, load=0.0, hot=(), block_size=4):
        self._load = load
        self.hot = list(hot)
        self.block_size = block_size
        self.alive = True
        self.inbox = []

    def load(self):
        return self._load

    def signals(self):
        return {"prefix_hot": self.hot, "prefix_block_size": self.block_size}

    def send(self, msg):
        self.inbox.append(msg)
        if msg["type"] == "request":
            self._load += 1

    def poll(self):
        return []


def test_router_prefix_affinity_beats_least_loaded():
    prompt = np.arange(8, dtype=np.int32)
    key = prefix_key(prompt[:4])
    router = FleetRouter(affinity=True)
    cold = _AffinityStub(load=0.0)
    warm = _AffinityStub(load=2.0, hot=[key])
    router.add_replica("cold", cold)
    router.add_replica("warm", warm)
    # warm is busier but advertises the prompt's first block: affinity
    # wins while the load gap stays under the guard
    rid = router.submit(prompt, 4)
    assert router.pending[rid]["replica"] == "warm"
    assert router.affinity_hits == 1 and router.affinity_overruled == 0
    stats = router.stats()
    assert stats["affinity"] and stats["affinity_hits"] == 1


def test_router_affinity_overruled_by_load_gap_and_off_switch():
    prompt = np.arange(8, dtype=np.int32)
    key = prefix_key(prompt[:4])
    router = FleetRouter(affinity=True, affinity_load_gap=8.0)
    router.add_replica("cold", _AffinityStub(load=0.0))
    router.add_replica("warm", _AffinityStub(load=20.0, hot=[key]))
    # affinity must never defeat balancing: 20 outstanding vs 0 is past
    # the gap, the global least-loaded pick wins
    rid = router.submit(prompt, 4)
    assert router.pending[rid]["replica"] == "cold"
    assert router.affinity_overruled == 1 and router.affinity_hits == 0
    # the A/B control arm: affinity off is pure least-loaded
    off = FleetRouter(affinity=False)
    off.add_replica("cold", _AffinityStub(load=0.0))
    off.add_replica("warm", _AffinityStub(load=2.0, hot=[key]))
    rid = off.submit(prompt, 4)
    assert off.pending[rid]["replica"] == "cold"
    assert off.stats()["affinity_hits"] == 0


def test_router_recent_dispatch_memory_colocates_bursts():
    # nobody advertises yet (tick lag): the first same-prefix request
    # lands least-loaded and is REMEMBERED; the burst follows it even
    # after the load tips the other way
    prompt = np.arange(8, dtype=np.int32)
    router = FleetRouter(affinity=True)
    a, b = _AffinityStub(load=0.0), _AffinityStub(load=0.5)
    router.add_replica("a", a)
    router.add_replica("b", b)
    r1 = router.submit(prompt, 4)
    assert router.pending[r1]["replica"] == "a"
    r2 = router.submit(prompt, 4)  # a now busier — but the prefix lives there
    assert router.pending[r2]["replica"] == "a"
    assert router.affinity_hits == 1


# ---------------------------------------------------------------------------
# the cache is host-side only: the decode program must not change
# ---------------------------------------------------------------------------

def test_decode_program_identical_cache_on_vs_off(engine_cfg):
    engine, cfg = engine_cfg

    def lowered(mode):
        sched = _mk_sched(engine, prefix_cache=mode)
        toks = np.zeros(sched.slots, np.int32)
        return sched.fns["decode"].lower(sched._serve_params, sched._cache, toks).as_text()

    on, off = lowered("on"), lowered("off")
    assert on == off  # byte-identical: zero device-side cost when idle
