"""graft-serve tier-1 gates (ISSUE 14): the continuous-batching scheduler
under a SIMULATED clock — admit/evict/chunk/speculate decisions over
scripted arrival traces with no wall-clock sleeps — plus the compiled-
program-churn regression, speculation losslessness, drain semantics, and
the sampling edge cases the serving path leans on."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine, sample_logits
from deepspeed_tpu.inference.serving import (ACTIVE, FINISHED, REFUSED,
                                             BlockPool,
                                             ContinuousBatchingScheduler,
                                             Request, ServingConfig)
from deepspeed_tpu.inference.serving.programs import prefill_rungs
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


class SimClock:
    """Deterministic tick counter: the scheduler's injected time source.
    Advances only when the test says so — no wall-clock sleeps anywhere."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt: float = 1.0):
        self.t += dt


def _fresh_engine(n_positions=128):
    cfg = get_gpt2_config("test", n_layer=2, n_positions=n_positions)
    icfg = DeepSpeedInferenceConfig(replace_with_kernel_inject=False)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    return InferenceEngine(GPT2LMHeadModel(cfg), icfg, topology=topo), cfg


@pytest.fixture(scope="module")
def engine_cfg():
    set_topology(None)
    engine, cfg = _fresh_engine()
    yield engine, cfg
    set_topology(None)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
            for p in lengths]


# ---------------------------------------------------------------------------
# the simulated-clock scheduler gate: scripted arrivals, no starvation,
# no KV-block leak
# ---------------------------------------------------------------------------
def test_scripted_trace_no_starvation_no_leak(engine_cfg):
    """A scripted arrival trace through admit/prefill/decode/retire: every
    request finishes (strict-FIFO admission cannot starve the head), block
    accounting balances to zero live blocks, and every request's greedy
    output matches offline ``engine.generate``."""
    engine, cfg = engine_cfg
    clock = SimClock()
    # pool sized to ~2 concurrent worst-case requests: admission pressure
    # is real, so the test exercises the blocked-head path too
    scfg = ServingConfig(slots=4, prefill_chunk=8, page_size=16,
                        kv_pool_tokens=128)
    sched = ContinuousBatchingScheduler(engine, scfg, clock=clock)
    lengths = [5, 20, 9, 33, 7, 13]
    arrival_at_tick = {0: [0, 1], 2: [2, 3], 5: [4, 5]}  # scripted trace
    reqs = [Request(prompt=p, max_new_tokens=6)
            for p in _prompts(cfg, lengths, seed=3)]

    tick = 0
    while any(not r.done for r in reqs):
        for i in arrival_at_tick.get(tick, []):
            sched.submit(reqs[i])
        kind = sched.step()
        clock.advance(1.0)
        tick += 1
        assert tick < 500, f"starved: states={[r.state for r in reqs]}"
        # invariant at EVERY tick: blocks reserved == blocks of live requests
        live = sched.pool.used_blocks
        expected = sum(sched.pool.blocks_for(r.total_tokens)
                       for r in reqs if r.state not in (FINISHED, REFUSED)
                       and r.state != "queued")
        assert live == expected, (tick, kind, live, expected)

    assert all(r.state == FINISHED for r in reqs)
    # no leak: the pool drains to empty and alloc/free balance
    c = sched.pool.counters()
    assert c["used_blocks"] == 0 and c["free_blocks"] == c["num_blocks"]
    assert c["total_allocs"] == c["total_frees"] == len(reqs)
    # latency evidence recorded on the simulated clock: TTFT is finite and
    # nondecreasing-by-arrival is NOT required, but every request has one
    assert sched.ttft_hist.count == len(reqs)
    assert all(r.ttft is not None and r.ttft >= 0 for r in reqs)
    # greedy parity request-by-request vs the offline engine
    for r in reqs:
        ref = np.asarray(engine.generate(r.prompt[None, :], max_new_tokens=6))
        assert r.output == list(ref[0, r.prompt_len:]), r.request_id


def test_admission_is_strict_fifo_under_block_pressure(engine_cfg):
    """A big head request must not be overtaken by small ones that would
    fit (no starvation by overtake); once it retires, the queue moves."""
    engine, cfg = engine_cfg
    clock = SimClock()
    # pool fits exactly one worst-case request at a time
    scfg = ServingConfig(slots=2, prefill_chunk=8, page_size=16,
                        kv_pool_tokens=48)
    sched = ContinuousBatchingScheduler(engine, scfg, clock=clock)
    big, small1, small2 = [Request(prompt=p, max_new_tokens=4)
                           for p in _prompts(cfg, [40, 6, 6], seed=4)]
    sched.submit(big)
    sched.run_until_drained(max_ticks=1)       # big admitted, starts prefill
    sched.submit(small1)
    sched.submit(small2)
    # while big is in flight the pool can't reserve small1 → strict FIFO
    # keeps BOTH smalls queued (small1 is the head; small2 must not overtake)
    assert big.state != FINISHED
    for _ in range(3):
        sched.step(); clock.advance(1.0)
    assert small1.state == "queued" and small2.state == "queued"
    sched.run_until_drained(max_ticks=200)
    assert [r.state for r in (big, small1, small2)] == [FINISHED] * 3
    # FIFO finish order follows arrival for the smalls
    order = [r.request_id for r in sched.finished]
    assert order.index(small1.request_id) < order.index(small2.request_id)


def test_oversize_request_refused_terminally(engine_cfg):
    engine, cfg = engine_cfg
    sched = ContinuousBatchingScheduler(engine, ServingConfig(slots=2))
    r = Request(prompt=_prompts(cfg, [100], seed=5)[0], max_new_tokens=100)
    sched.submit(r)  # 200 > 128 context capacity
    assert r.state == REFUSED and "exceeds context capacity" in r.refuse_reason
    assert len(sched.queue) == 0 and sched.queue.refused == 1


def test_chunked_prefill_interleaves_decode(engine_cfg):
    """A long prompt arriving while another request decodes must NOT stall
    it: with prefill_interleave=1 the tick kinds alternate prefill/decode
    until the long prompt completes — and the math is unchanged."""
    engine, cfg = engine_cfg
    clock = SimClock()
    scfg = ServingConfig(slots=4, prefill_chunk=8, prefill_interleave=1)
    sched = ContinuousBatchingScheduler(engine, scfg, clock=clock)
    short, long_ = [Request(prompt=p, max_new_tokens=10)
                    for p in _prompts(cfg, [6, 61], seed=6)]  # 8 chunks for long
    sched.submit(short)
    sched.step(); clock.advance(1.0)           # short prefills, goes ACTIVE
    assert short.state == ACTIVE
    sched.submit(long_)
    kinds = []
    while not long_.done or not short.done:
        kinds.append(sched.step()); clock.advance(1.0)
        assert len(kinds) < 300
    # while both were live, no two consecutive prefill ticks: decodes ran
    # between every pair of prefill chunks (the no-stall contract)
    for a, b in zip(kinds, kinds[1:]):
        assert not (a == "prefill" and b == "prefill")
    assert kinds.count("prefill") >= 8          # the long prompt's chunks
    for r in (short, long_):
        ref = np.asarray(engine.generate(r.prompt[None, :], max_new_tokens=10))
        assert r.output == list(ref[0, r.prompt_len:])


def test_eos_retires_slot_and_frees_blocks(engine_cfg):
    engine, cfg = engine_cfg
    sched = ContinuousBatchingScheduler(engine, ServingConfig(slots=2))
    prompt = _prompts(cfg, [4], seed=7)[0]
    first = int(np.asarray(engine.generate(prompt[None, :], max_new_tokens=1))[0, -1])
    r = Request(prompt=prompt, max_new_tokens=8, eos_token_id=first)
    sched.submit(r)
    sched.run_until_drained(max_ticks=50)
    assert r.state == FINISHED and r.output == [first]  # stopped at eos
    assert sched.pool.used_blocks == 0


# ---------------------------------------------------------------------------
# satellite: _pow2_bucket recompile churn — N requests spanning two buckets
# compile exactly two serving program sets, and schedulers reuse the cache
# ---------------------------------------------------------------------------
def test_two_slot_buckets_compile_two_program_sets():
    engine, cfg = _fresh_engine()
    outs = {}
    # 4 deployments spanning two pow2 buckets: 3→4, 6→8, 4→4, 8→8.
    # The 21-token prompt makes every program re-run against an EVOLVED
    # cache (2 prefill ticks + decodes), so a sharding/aval drift between
    # the fresh cache and program outputs would show as a second compile.
    for slots in (3, 6, 4, 8):
        sched = ContinuousBatchingScheduler(engine, ServingConfig(slots=slots))
        assert sched.slots == engine._pow2_bucket(slots)
        # warmup's parked-cache calls must hit the SAME compiled programs
        # the ticks use — an aval/sharding drift would show as a 2nd compile
        sched.warmup()
        r = Request(prompt=np.arange(21, dtype=np.int32) % cfg.vocab_size,
                    max_new_tokens=3)
        sched.submit(r)
        sched.run_until_drained(max_ticks=50)
        outs[slots] = r.output
    # exactly TWO cached program sets (bucket 4 and bucket 8), not four
    buckets = {key[2] for key in engine._serve_cache}
    assert buckets == {4, 8}, sorted(engine._serve_cache)
    assert len(engine._serve_cache) == 2
    # and each jitted program compiled exactly once across all deployments
    # (``prefill_rung``: one function, a program a rung below the whole)
    for key, fns in engine._serve_cache.items():
        for name, fn in fns.items():
            entries = len(prefill_rungs(key[2])) - 1 if name == "prefill_rung" else 1
            assert fn._cache_size() == entries, (name, fn._cache_size())
    # bucketing never changes results
    assert outs[3] == outs[4] and outs[6] == outs[8]


def _kd_drafter(engine, cfg, n_layer=1):
    """The in-tree drafter the ISSUE names: a layer-reduced KD student
    seeded from the target's own layers (compression/compress.py)."""
    import flax.linen as nn

    from deepspeed_tpu.compression.compress import student_initialization
    dcfg = get_gpt2_config("test", n_layer=n_layer,
                           n_positions=cfg.n_positions)
    drafter = GPT2LMHeadModel(dcfg)
    d_init = nn.meta.unbox(drafter.init(jax.random.PRNGKey(1),
                                        np.zeros((1, 8), np.int32))["params"])
    d_params = student_initialization(
        d_init, jax.device_get(nn.meta.unbox(engine.params)),
        {"compression_training": {"layer_reduction": {
            "enabled": True, "module_name_prefix": "h", "teacher_layer": [0],
            "other_module_name": ["wte", "wpe", "ln_f"]}}})
    return drafter, d_params


def test_speculative_decoding_is_lossless_greedy(engine_cfg):
    """Greedy output with speculation ON is token-identical to speculation
    OFF, and acceptance is accounted per request and in aggregate."""
    engine, cfg = engine_cfg
    drafter = _kd_drafter(engine, cfg)
    prompts = _prompts(cfg, [5, 12, 9, 17], seed=8)

    def run(spec):
        scfg = ServingConfig(slots=4, prefill_chunk=8,
                            speculation={"enabled": spec, "k": 3})
        sched = ContinuousBatchingScheduler(
            engine, scfg, drafter=drafter if spec else None, clock=SimClock())
        sched.warmup()  # compiles everything up front, incl. refeed verify
        reqs = [Request(prompt=p, max_new_tokens=9) for p in prompts]
        for r in reqs:
            sched.submit(r)
        sched.run_until_drained(max_ticks=2000)
        # warmup reached every program with tick-identical avals — nothing
        # recompiled mid-run (incl. the drafter's rare full-k refeed verify,
        # which a warm request cannot reliably trigger)
        for fns in (sched.fns,) + ((sched.dfns,) if spec else ()):
            for name, fn in fns.items():
                if spec and fns is sched.fns and name == "decode":
                    # dead under speculation (step() always spec-ticks):
                    # warmup deliberately skips its compile
                    assert fn._cache_size() == 0, (name, fn._cache_size())
                    continue
                entries = len(sched._rungs) - 1 if name == "prefill_rung" else 1
                assert fn._cache_size() == entries, (name, fn._cache_size())
        return reqs, sched.stats()

    base_reqs, base_stats = run(False)
    spec_reqs, spec_stats = run(True)
    assert [r.output for r in spec_reqs] == [r.output for r in base_reqs]
    # acceptance accounting: aggregate + per-request, and it rides stats()
    assert spec_stats["drafted"] > 0
    assert 0.0 <= spec_stats["acceptance_rate"] <= 1.0
    assert spec_stats["drafted"] == sum(r.drafted_tokens for r in spec_reqs)
    assert spec_stats["accepted"] == sum(r.accepted_tokens for r in spec_reqs)
    for r in spec_reqs:
        assert r.acceptance_rate is not None
        assert "acceptance_rate" in r.stats()
    # a decent drafter (the KD student IS the target's layer here) should
    # accept a non-trivial fraction — speculation that never accepts is a
    # wiring bug, not a quality question
    assert spec_stats["acceptance_rate"] > 0.2
    # fewer target decode ticks than emitted tokens = the speedup mechanism
    emitted = sum(len(r.output) for r in spec_reqs)
    assert spec_stats["ticks"]["spec"] < emitted


def test_speculation_requires_greedy_and_drafter():
    with pytest.raises(ValueError, match="lossless under greedy"):
        ServingConfig(do_sample=True, speculation={"enabled": True})
    engine, _ = _fresh_engine()
    with pytest.raises(ValueError, match="needs a drafter"):
        ContinuousBatchingScheduler(
            engine, ServingConfig(speculation={"enabled": True}))


# ---------------------------------------------------------------------------
# drain semantics: SIGTERM-shaped preemption via the PR-9 guard
# ---------------------------------------------------------------------------
def test_drain_finishes_in_flight_refuses_queued_returns_143(engine_cfg):
    """The drain contract in-process (the subprocess SIGTERM leg lives in
    tools/fault_bench.py scenario_serve_drain): a preemption request
    mid-serve finishes every in-flight request, terminally refuses the
    queue, and serve() returns 143."""
    from deepspeed_tpu.runtime.resilience.signals import PreemptionGuard
    engine, cfg = engine_cfg
    clock = SimClock()
    sched = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=2, prefill_chunk=8), clock=clock)
    reqs = [Request(prompt=p, max_new_tokens=12)
            for p in _prompts(cfg, [6, 7, 8, 9, 10], seed=9)]
    guard = PreemptionGuard(signals=[])  # flag-only: no handler install
    orig_step = sched.step
    ticks = {"n": 0}

    def stepping(admit=True):
        ticks["n"] += 1
        if ticks["n"] == 3:          # preempt mid-flight, off any boundary
            guard.request("SIGTERM")
        return orig_step(admit=admit)

    sched.step = stepping
    rc = sched.serve(reqs, guard=guard)
    assert rc == 143
    finished = [r for r in reqs if r.state == FINISHED]
    refused = [r for r in reqs if r.state == REFUSED]
    assert len(finished) + len(refused) == len(reqs) and refused
    # in-flight requests DRAINED: full budget, not truncated mid-decode
    for r in finished:
        assert len(r.output) == r.max_new_tokens
    for r in refused:
        assert "draining" in r.refuse_reason
    assert sched.pool.used_blocks == 0  # drain leaks nothing


def test_serve_completes_clean_returns_zero(engine_cfg):
    engine, cfg = engine_cfg
    sched = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=2), clock=SimClock())
    reqs = [Request(prompt=p, max_new_tokens=3)
            for p in _prompts(cfg, [5, 6], seed=10)]
    from deepspeed_tpu.runtime.resilience.signals import PreemptionGuard
    assert sched.serve(reqs, guard=PreemptionGuard(signals=[])) == 0
    assert all(r.state == FINISHED for r in reqs)


# ---------------------------------------------------------------------------
# satellite: BlockPool accounting (the admission-control currency)
# ---------------------------------------------------------------------------
def test_block_pool_accounting_counters():
    pool = BlockPool(num_blocks=8, block_size=4)
    assert pool.blocks_for(0) == 0 and pool.blocks_for(1) == 1
    assert pool.blocks_for(4) == 1 and pool.blocks_for(5) == 2
    pool.reserve(1, 10)                       # 3 blocks, 12 token slots
    pool.advance(1, 10)
    assert pool.used_blocks == 3 and pool.free_blocks == 5
    assert pool.fragmentation_tokens() == 2   # block-rounding waste
    pool.reserve(2, 20)                       # 5 blocks: pool now full
    assert not pool.can_allocate(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.reserve(3, 1)
    assert 3 not in pool.live_sequences()     # failed reserve rolls back
    pool.free(1)
    assert pool.can_allocate(12)
    c = pool.counters()
    assert c["peak_used_blocks"] == 8
    assert c["total_allocs"] == 3 and c["total_frees"] == 2
    pool.free(2)
    assert pool.counters()["free_blocks"] == 8


def test_paged_kv_exposes_pool_counters():
    """PagedKVCache delegates allocator bookkeeping to the shared BlockPool
    so admission control and the paged cache report one accounting."""
    from deepspeed_tpu.inference.paged_kv import PagedKVCache
    cache = PagedKVCache(num_pages=8, page_size=4, num_heads=1, head_dim=2)
    cache.allocate(0)
    cache.append(0, jnp.ones((6, 1, 2)), jnp.ones((6, 1, 2)))
    c = cache.counters()
    assert c["used_blocks"] == 2 and c["total_allocs"] == 1
    assert c["fragmentation_tokens"] == 2     # 8 slots held, 6 used
    cache.free(0)
    c = cache.counters()
    assert c["free_blocks"] == 8 and c["total_frees"] == 1


# ---------------------------------------------------------------------------
# satellite: sample_logits top-p edge cases (empty nucleus pinned)
# ---------------------------------------------------------------------------
class TestTopPEdgeCases:
    def _logits(self):
        # one clearly-dominant token so argmax is unambiguous
        logits = np.full((3, 16), -4.0, np.float32)
        logits[:, 5] = 8.0
        return jnp.asarray(logits)

    def test_empty_nucleus_low_temperature_falls_back_to_argmax(self):
        """Low temperature concentrates cum[0] ~ 1.0 > top_p: the nucleus
        is empty. Pinned behavior: single-token argmax fallback — never a
        NaN renormalization over empty support."""
        logits = self._logits()
        for seed in range(5):
            tok = sample_logits(logits, jax.random.PRNGKey(seed), True,
                                temperature=0.01, top_k=0, top_p=0.05)
            assert tok.tolist() == [5, 5, 5]

    def test_top_p_zero_falls_back_to_argmax(self):
        logits = self._logits()
        tok = sample_logits(logits, jax.random.PRNGKey(0), True,
                            temperature=1.0, top_k=0, top_p=0.0)
        assert tok.tolist() == [5, 5, 5]

    def test_top_p_near_one_stays_in_vocab_bounds(self):
        """cum can stay strictly below top_p through the whole vocab under
        rounding; the clipped cutoff index must not walk off the axis."""
        flat = jnp.zeros((2, 8))              # uniform: worst rounding case
        tok = sample_logits(flat, jax.random.PRNGKey(1), True,
                            temperature=1.0, top_p=1.0 - 1e-9, top_k=0)
        assert ((0 <= tok) & (tok < 8)).all()

    def test_top_p_filters_tail(self):
        """Sanity: a real nucleus (two likely tokens) excludes the tail."""
        logits = np.full((1, 16), -10.0, np.float32)
        logits[:, 3] = 5.0
        logits[:, 7] = 5.0
        toks = {int(sample_logits(jnp.asarray(logits), jax.random.PRNGKey(s),
                                  True, temperature=1.0, top_k=0, top_p=0.9)[0])
                for s in range(20)}
        assert toks <= {3, 7} and len(toks) == 2
