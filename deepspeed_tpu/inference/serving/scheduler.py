"""graft-serve: continuous in-flight batching over the per-slot decode
cache (ISSUE 14 / ROADMAP item 1 — the latency-under-load axis).

One scheduler drives one target :class:`InferenceEngine` through three
fixed-shape programs (``serving/programs.py``): requests join and leave
decode slots on every tick without changing any compiled shape; chunked
prefill interleaves long prompts with in-flight decodes; speculative
decoding drafts with the compression/KD student and verifies in one
batched target pass. Admission is block-pool truthful (``queue.py``):
a request is admitted only when its worst-case KV footprint is
reservable, so nothing dies mid-flight and nothing leaks.

Host protocol (the part that makes rollback and join/leave free): the
scheduler's numpy ``lengths`` mirror is authoritative — every tick hands
it to its program as the ``write_pos`` operand, beside the tick's other
small inputs, as host arrays the jitted call's own dispatch carries: the
host never writes into the cache between ticks. A parked slot carries
the sentinel position (= slot capacity) so its writes drop out of
bounds; a rejected speculation simply never advances the mirror past the
accepted prefix.

Integration seams (the five the last PRs built):
* resilience — :meth:`serve` wires a ``PreemptionGuard``; SIGTERM drains
  in-flight requests (finish), refuses the queue, and returns exit 143.
* tracing — every tick and its host phases are spans of the process's
  recorder (``utils/trace.py``), always; per-request latency/acceptance
  events ride a ``RuntimeTelemetry`` sink when one is attached.
* graft-audit — the decode program is the ``serve_decode_step`` scenario
  (same ``make_apply_fn``), budgeted and signature-pinned by R009/R010/R013.
* compression — the drafter is the KD student
  (``compression.compress.student_initialization``).
* engine — programs live in the engine's bucketed ``_serve_cache``.
"""

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax

from deepspeed_tpu.inference.serving.blocks import BlockPool
from deepspeed_tpu.inference.serving.config import ServingConfig
from deepspeed_tpu.inference.serving.programs import (POOL_LEAVES, RING_LEAVES, TOKEN_LEAF,
                                                      _leaf_name, counter_widths, decode_rungs,
                                                      has_recurrent_state, has_ring,
                                                      make_slot_cache, prefill_rungs,
                                                      serve_programs, slot_capacity,
                                                      state_bytes_per_slot)
from deepspeed_tpu.inference.serving.queue import RequestQueue
from deepspeed_tpu.inference.serving.request import (ACTIVE, FINISHED, PREFILL,
                                                     Request)
from deepspeed_tpu.models.common import (KV_READS, PASS_READS, SPARSE_READS,
                                         slot_pool_positions_touched, slot_pool_row_shape,
                                         slot_pool_rows, slot_pool_set_rows)
from deepspeed_tpu.runtime.telemetry.metrics import Histogram
from deepspeed_tpu.utils import trace
from deepspeed_tpu.utils.logging import log_dist


class MigrationError(RuntimeError):
    """Live KV migration refused or failed verification (graft-fleet).

    Raised loudly instead of degrading: a half-migrated request is worse
    than a drained one, so callers (``serve``'s migrate hook, the fleet
    router) fall back to the PR-14 drain contract when they see it."""


#: request states the migration codec can serialize: a PREFILL request's
#: state is fully described by (prompt, prefill_pos, committed KV); an
#: ACTIVE one adds (output, next_token). QUEUED requests never migrate —
#: they have no KV and are simply re-admitted by the router.
MIGRATABLE_STATES = (PREFILL, ACTIVE)


def _quant_view(module, params, weight_dtype: str, group_size: int):
    """graft-quant-serve: the (quant module, params bundle) pair a
    quantized serving path closes over. The module is rebuilt with
    ``serve_weight_dtype`` set — projections must statically declare the
    code layout the param tree actually carries (int4 halves the
    contraction axis). Refuses model families without the seam rather
    than silently serving fp."""
    import dataclasses

    from deepspeed_tpu.ops.quantizer.weights import quantize_params
    cfg = getattr(module, "config", None)
    if (cfg is None or not dataclasses.is_dataclass(cfg)
            or not any(f.name == "serve_weight_dtype"
                       for f in dataclasses.fields(cfg))):
        raise NotImplementedError(
            f"{type(module).__name__} does not declare the serve_weight_dtype "
            f"seam — weight-quantized serving needs projections that read "
            f"int8/int4 kernels (models/gpt2.py pattern)")
    q_module = type(module)(dataclasses.replace(cfg, serve_weight_dtype=weight_dtype))
    qparams, qscales = quantize_params(params, weight_dtype, group_size)
    return q_module, {"params": qparams, "quant": qscales}


def _restore_rows_jit_impl(flat_cache, rows, slot, token, kv_idx, token_idx):
    out = list(flat_cache)
    for j, i in enumerate(kv_idx):
        out[i] = slot_pool_set_rows(out[i], slot, rows[j])
    out[token_idx] = out[token_idx].at[slot].set(token)
    return out


#: One program writes every KV leaf's restored rows into a slot, and the
#: token the slot is fed next, with the
#: cache DONATED so XLA updates the pool buffers in place. ``slot`` rides
#: as a traced scalar (no per-slot recompile); the row length keys the
#: jit cache through the row shapes. Restores happen per prefix-cache
#: admission, so the eager alternative — per-leaf ``.at[].set``, each
#: copying the entire pool — is a serving-throughput bug, not a style
#: choice.
_restore_rows_jit = jax.jit(_restore_rows_jit_impl,
                            static_argnums=(4, 5), donate_argnums=(0,))


class _Program:
    """One dispatched program until its tokens are read: ``ran`` the
    sequences it ran (its rung), ``rows`` the
    requests it serves, each ``(slot, request, row of ``tok``, prompt tokens
    fed, whether the row's token is the request's next)``; ``ending`` the
    ``(slot, request)`` whose last token, by count, this program samples."""

    __slots__ = ("kind", "ran", "tok", "rows", "ending")

    def __init__(self, kind: str, ran: int, tok, rows: list, ending: list):
        self.kind, self.ran, self.tok, self.rows, self.ending = kind, ran, tok, rows, ending


class ContinuousBatchingScheduler:
    """Continuous (in-flight) batching over one target engine.

    ``drafter``: optional ``(flax module, params)`` — the speculation
    drafter (typically the layer-reduced KD student). Required when
    ``config.speculation.enabled``.

    ``clock``: injectable time source (``time.monotonic`` default); the
    tier-1 scheduler test drives a simulated clock with no wall sleeps.
    """

    def __init__(self, engine, config=None, drafter: Optional[Tuple] = None,
                 clock: Optional[Callable[[], float]] = None, telemetry=None,
                 seed: int = 0):
        self.telemetry = telemetry
        # spans and counters go to the process's recorder whether or not a
        # sink is attached; with one, under its source, so its window flush
        # reads this scheduler's records
        self._rec = trace.recorder()
        self._source = (telemetry.source if telemetry is not None
                        else trace.new_source("sched"))
        self._tick_no = 0
        with self._rec.span("scheduler_init", source=self._source, marks=trace.TOTAL):
            self._build(engine, config, drafter, clock, seed)

    def _build(self, engine, config, drafter, clock, seed) -> None:
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig(**config)
        self.config = config
        self.engine = engine
        self.module = engine.module
        self.clock = clock or time.monotonic
        # explicit host-to-device transfers issued inside ticks; a tick's
        # inputs ride its program's own dispatch, so it reads 0 without
        # speculation (shown as 0, not left out)
        self._rec.count("tick_input_puts", 0)
        # programs dispatched, those of them dispatched while another was in
        # flight, read-backs forced before their time (also by reason:
        # ``ticks_settled_<spec|prefix|export|swap|drain>``), rows run for
        # a request that had already ended on its EOS, and the programs
        # dispatched ahead that reached a device with nothing queued (``_probe``)
        for name in ("ticks_dispatched", "ticks_dispatched_ahead", "ticks_settled",
                     "slot_ticks_discarded", "ticks_device_dry"):
            self._rec.count(name, 0)
        # an expert model says what its expert matmuls owe and are given
        # (``moe_rows``); a dense model has no such counters
        self._moe_rows = getattr(engine.module, "moe_rows", None)

        # graft-quant-serve: when the configuration serves quantized, swap
        # in the quant module + code/scale bundle every program below
        # closes over. The engine's own params stay fp.
        self.weight_dtype = config.weight_dtype
        self.kv_quant = bool(config.kv_quant)
        self._serve_params = engine.params
        if self.weight_dtype != "fp":
            if getattr(engine, "_wq_scales", None) is not None:
                raise ValueError(
                    "engine already serves an int8 weight view (engine quant "
                    "config); serving.weight_dtype would double-quantize — "
                    "enable one of the two")
            self.module, self._serve_params = _quant_view(
                engine.module, engine.params, self.weight_dtype,
                config.weight_group_size)

        # pow2 slot bucket: alternating deployments reuse compiled programs
        self.slots = engine._pow2_bucket(config.slots)
        # the fresh cache must carry the SAME engine-mesh sharding its
        # steady-state successors (program outputs) will: a bare
        # make_slot_cache is SingleDeviceSharding, and the first tick fed
        # the evolved NamedSharding cache would silently recompile every
        # program (~0.7 s mid-serve, measured as request 0's TTFT tail)
        from jax.sharding import NamedSharding, PartitionSpec
        self._placement = NamedSharding(engine.mesh, PartitionSpec())
        with self._phase("cache_alloc"):
            self._cache = jax.device_put(  # graft-lint: waive R008 jax-owned fresh cache zeros, never donated before first use
                make_slot_cache(self.module, self.slots, kv_quant=self.kv_quant),
                self._placement)
        self.capacity = slot_capacity(self._cache)  # tokens per slot
        # a model with recurrent layers keeps per-slot state with no
        # positions: no rows to copy, no length to leave unadvanced. What
        # assumes rows refuses it by name until the state has snapshots
        self._recurrent = has_recurrent_state(self._cache)
        self._state_bytes = state_bytes_per_slot(self._cache)
        # what the programs count for the host behind a tick's tokens
        self._counters = counter_widths(self._cache)
        # a looped stack runs its layers several times a tick over one set of
        # weights and says what a decode tick streams of them (``loop_weight_bytes``)
        self._loop_passes = int(getattr(getattr(self.module, "config", None), "loop_passes", 1))
        if self._loop_passes > 1:
            self._loop_stream_bytes = self.module.loop_weight_bytes(self._serve_params)
        if self._recurrent and config.prefix_cache == "on":
            raise NotImplementedError(
                f"prefix_cache='on' over {type(self.module).__name__}: a shared prefix is "
                f"restored as cache rows at positions, and this model's recurrent state "
                f"(ssm_state / conv_state) has no rows: sharing it needs a snapshot of the "
                f"state at the prefix's end, which is not built")
        if self._recurrent and (config.speculation.enabled or drafter is not None):
            raise NotImplementedError(
                f"speculative decoding over {type(self.module).__name__}: a rejected draft is "
                f"rolled back by not advancing a length, and this model's recurrent state "
                f"has already advanced over the drafted tokens: verification needs the "
                f"state at the accepted position, which is not built")

        if getattr(getattr(self.module, "config", None), "head_last_fed_only", False) and (
                config.speculation.enabled or drafter is not None):
            raise NotImplementedError(
                f"speculative decoding over {type(self.module).__name__} with "
                f"head_last_fed_only: the verify step reads the target's token at every "
                f"drafted position, and this model makes a chunk's logits for its last real "
                f"token alone: serve it with head_last_fed_only=False to speculate")
        # a window layer's ring has overwritten most positions' rows: what
        # copies rows by position refuses it by name until it carries the
        # window (the last ``window - 1`` rows and where the ring stands)
        self._ring = has_ring(self._cache)
        rings = self._ring_names = ", ".join(sorted(
            {_leaf_name(path) for path, _ in jax.tree_util.tree_flatten_with_path(self._cache)[0]
             if _leaf_name(path) in RING_LEAVES}))
        if self._ring and config.prefix_cache == "on":
            raise NotImplementedError(
                f"prefix_cache='on' over {type(self.module).__name__}: a shared prefix is "
                f"restored as cache rows at positions, and this model's window layers keep a "
                f"ring ({rings}) that has overwritten them: sharing needs the "
                f"window's rows at the prefix's end in the block, which is not built")
        if self._ring and (config.speculation.enabled or drafter is not None):
            raise NotImplementedError(
                f"speculative decoding over {type(self.module).__name__}: the verify step "
                f"writes k + 1 positions into this model's window rings "
                f"({rings}), which are sized for the window and a prefill chunk "
                f"and have not been checked against a rejected draft's rewrite: not built")

        # admission: block-pool truthful KV accounting. A byte budget is
        # sized into tokens from the cache's ACTUAL per-token footprint
        # (int8 codes + scales under kv_quant), which is how quantized KV
        # turns the same HBM into more blocks and deeper admission.
        pool_tokens = config.kv_pool_tokens or self.slots * self.capacity
        if config.kv_pool_bytes:
            pool_tokens = max(config.page_size,
                              int(config.kv_pool_bytes /
                                  max(1.0, self._kv_bytes_per_token())))
        # graft-prefix-cache: content-address the pool. The hash envelope
        # folds in every knob that makes cached KV bytes non-reusable —
        # kv_quant changes the stored codes/scales, the served weight
        # dtype changes the values prefill computes, speculation adds a
        # drafter cache role the payload must also carry.
        self.prefix_cache = config.prefix_cache
        self.spec_k = int(config.speculation.k) if config.speculation.enabled else 0
        envelope = (f"kvq:{int(self.kv_quant)}/wq:{self.weight_dtype}"
                    f"/spec:{self.spec_k}")
        self.pool = BlockPool(num_blocks=max(1, pool_tokens // config.page_size),
                              block_size=config.page_size,
                              prefix_cache=self.prefix_cache == "on",
                              envelope=envelope)
        self.queue = RequestQueue(self.pool, max_queue=config.max_queue,
                                  max_total_tokens=self.capacity, clock=self.clock)

        if self.spec_k and drafter is None:
            raise ValueError("speculation.enabled needs a drafter: pass "
                             "drafter=(module, params) — e.g. the KD student from "
                             "compression.student_initialization")
        sampling = dict(do_sample=config.do_sample, temperature=config.temperature,
                        top_k=config.top_k, top_p=config.top_p)
        quantized = self.weight_dtype != "fp"
        with self._phase("serve_programs"):
            self.fns = serve_programs(engine, self.slots,
                                      module=self.module if quantized else None,
                                      mparams=(lambda p: p) if quantized else None,
                                      prefill_chunk=config.prefill_chunk,
                                      spec_k=self.spec_k,
                                      weight_dtype=self.weight_dtype if quantized else None,
                                      **sampling)
        with self._phase("probe"):
            self._probe_slot_decode()
        self._drafter = None
        if drafter is not None and self.spec_k:
            d_module, d_params = drafter
            d_weight_dtype = None
            if quantized:
                # the drafter rides int8 whenever the target serves
                # quantized: speculation gets cheaper in the same units
                d_module, d_params = _quant_view(d_module, d_params, "int8",
                                                 config.weight_group_size)
                d_weight_dtype = "int8"
            self._drafter = (d_module, jax.device_put(d_params))  # graft-lint: waive R008 drafter weights, never donated
            with self._phase("cache_alloc"):
                self._drafter_cache = jax.device_put(  # graft-lint: waive R008 jax-owned fresh cache zeros, same placement contract as the target cache
                    make_slot_cache(d_module, self.slots, kv_quant=self.kv_quant),
                    self._placement)
            if slot_capacity(self._drafter_cache) < self.capacity:
                raise ValueError("drafter context capacity is smaller than the "
                                 "target's — it cannot draft to the end of a "
                                 "maximal request")
            with self._phase("serve_programs"):
                self.dfns = serve_programs(engine, self.slots, role="drafter",
                                           module=d_module, mparams=lambda p: p,
                                           prefill_chunk=config.prefill_chunk,
                                           spec_k=self.spec_k,
                                           weight_dtype=d_weight_dtype,
                                           **sampling)
        # the sequence counts the prefill program exists at, ascending: a
        # prefill tick runs the smallest that holds the slots it feeds (the
        # drafter's program is fed the same operands: the shorter ladder)
        caches = [self._cache] + ([self._drafter_cache] if self._drafter is not None else [])
        self._rungs = min((prefill_rungs(self.slots, engine.mesh.size, cache)
                           for cache in caches), key=len)
        # and the plain decode program's, which a speculating scheduler never runs
        self._decode_rungs = ((self.slots,) if self.spec_k
                              else decode_rungs(self.slots, engine.mesh.size, self._cache))

        # what every call of a target program is handed besides its tick's few
        # host arrays: the served tree and the slot cache, leaf by leaf
        handed = jax.tree_util.tree_leaves((self._serve_params, self._cache))
        self._rec.gauge("program_operand_leaves", len(handed))
        self._rec.gauge("program_operand_bytes",
                        sum(leaf.size * leaf.dtype.itemsize for leaf in handed))

        # host-side authoritative slot state, as of what has been DISPATCHED:
        # who holds a slot, the cache positions written, the prompt tokens fed
        # and the output tokens whose sampling is under way or done
        self._slot_req: List[Optional[Request]] = [None] * self.slots
        self._lengths = np.full(self.slots, self.capacity, np.int64)  # parked sentinel
        self._fed = np.zeros(self.slots, np.int64)
        self._sent = np.zeros(self.slots, np.int64)
        # the program whose tokens have not been read, and why this scheduler
        # reads every program in the step that dispatched it, if it must: a
        # drafter's accept loop reads tokens, a prefix publish copies pool rows
        self._inflight: Optional[_Program] = None
        # of the tick in progress: when the program in flight was last looked
        # at, and (when, the look before, the phase between) of the first look
        # that found it ended (``_probe``)
        self._probed, self._dry = 0.0, None
        self._serial = ("spec" if self._drafter is not None
                        else "prefix" if self.prefix_cache == "on" else None)
        self._decode_ticks_since_prefill = 10**9  # first prefill never waits
        self._rng = jax.random.PRNGKey(seed)

        # evidence: latency histograms + tick/speculation counters
        self.ttft_hist = Histogram()
        self.tok_hist = Histogram()
        self.ticks = {"prefill": 0, "decode": 0, "spec": 0, "idle": 0}
        # achieved-throughput clock zero: the first non-idle tick, so an
        # idle replica's achieved_tok_s reads None instead of decaying
        self._serve_t0: Optional[float] = None
        self.drafted_total = 0
        self.accepted_total = 0
        self.finished: List[Request] = []
        # graft-rlhf rollout evidence: experience completed through this
        # scheduler, learner steps the rollout loop interleaved while
        # requests were in flight, and the weight-sync generation counter
        # (bumped by swap_served_params — 0 means construction weights)
        self.rollout_experience = 0
        self.learner_steps_overlapped = 0
        self.weight_sync_generation = 0
        self.last_weight_sync: Optional[dict] = None
        log_dist(f"graft-serve: slots={self.slots} capacity={self.capacity} "
                 f"pool={self.pool.num_blocks}x{self.pool.block_size} "
                 f"chunk={config.prefill_chunk} wq={self.weight_dtype} "
                 f"kv_quant={self.kv_quant} "
                 f"spec_k={self.spec_k} prefix_cache={self.prefix_cache}")

    # ------------------------------------------------------------------
    def _probe_slot_decode(self) -> None:
        """Fail at construction — with the model family named — when the
        module's decode path cannot take a per-slot index vector (only
        families whose attention appends through ``models/common.py``'s
        ``DecodeCache`` or ``LatentCache`` can serve). The probe is the
        decode program's own trace, over the operands ``warmup`` and every
        decode tick hand it, so the first of those calls finds the program
        traced: a scheduler pays for one trace of the model here, not two.
        Where that trace fails, the model's step alone over a [slots, 1]
        batch says whether the family refused it; a fault of anything
        around the model (the sampler, the counters) is raised as it is."""
        tokens = np.zeros(self.slots, np.int32)
        rng = (jax.random.PRNGKey(0),) if self.config.do_sample else ()
        try:
            jax.eval_shape(self.fns["decode"], self._serve_params, self._cache, tokens, *rng)
        except Exception:
            from deepspeed_tpu.inference.serving.programs import (make_apply_fn,
                                                                  without_next_tokens)
            step, ids = make_apply_fn(self.module), tokens[:, None]
            try:
                jax.eval_shape(lambda p, c: step(p, without_next_tokens(c)[0], ids),
                               self._serve_params, self._cache)
            except Exception as e:
                raise NotImplementedError(
                    f"{type(self.module).__name__} does not support the per-slot "
                    f"(ragged) decode cache graft-serve schedules against — its "
                    f"decode path rejected a [slots] cache_index vector: "
                    f"{type(e).__name__}: {e}") from e
            raise

    def _kv_bytes_per_token(self) -> float:
        """Measured KV bytes per cached token, straight off the slot
        cache's pool (+ scale) leaves — the unit that converts a byte
        budget into admission depth and prices bytes-per-KV-block in the
        bench rows. Int8 KV: 1 code byte per element plus the per-(slot,
        position, head) scale, vs the fp pool's full element width."""
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self._cache)[0]:
            name = _leaf_name(path)
            if name in POOL_LEAVES + RING_LEAVES or name.endswith("_scale"):
                total += leaf.size * leaf.dtype.itemsize
        return total / float(self.slots * self.capacity)

    def _read_back(self, tok, kind: str) -> np.ndarray:
        """The tick's blocking read-back: the tokens, one a sequence the
        program ran ([slots], or a prefill rung's fewer), and behind
        them whatever the program counted for the host
        (``programs.with_counters``). ``moe_rows``: an expert layer that
        holds a share of its experts decides on the device which rows are
        its own. Rows and experts touched are also kept by the kind of tick,
        for whoever works out what a tick of that kind had to stream, and so
        are the rows of the buffers the layers sized for their routed rows.
        ``latent_reads``: the positions of its pools a latent attention's
        loops were bounded to, against the positions that held a token, by
        the kind of tick, and the bytes it wrote there. ``moe_group_rows``:
        a router that limits a token to some groups of experts says how many
        real rows could reach this device's experts at all."""
        tok = np.asarray(tok)
        ran = len(tok) - sum(width for _, width in self._counters)
        behind = tok[ran:]
        for name, width in self._counters:
            counted, behind = [int(n) for n in behind[:width]], behind[width:]
            if name == "moe_rows":
                here, computed, anywhere, touched, buffered = counted
                self._rec.count("moe_rows_routed", here)
                self._rec.count("moe_rows_elsewhere", anywhere - here)
                self._rec.count("moe_rows_computed", computed)
                self._rec.count("moe_rows_buffered", buffered)
                self._rec.count(f"moe_rows_routed_{kind}", here)
                self._rec.count(f"moe_rows_buffered_{kind}", buffered)
                self._rec.count("moe_experts_touched", touched)
                self._rec.count(f"moe_experts_touched_{kind}", touched)
            elif name == "latent_reads":
                read, live, written = counted
                self._rec.count(f"latent_positions_read_{kind}", read)
                self._count_of_tick("latent_positions_live", live, kind)
                self._rec.count("latent_bytes_written", written)
            elif name == "sparse_reads":
                # ``models/common.py`` SPARSE_READS: an indexed layer's index
                # keys read and positions chosen of positions live, a window
                # layer's ring positions read and in a window; bytes by pool
                for what, n in zip(SPARSE_READS, counted):
                    if what.endswith("_written"):
                        self._rec.count(what, n)
                    else:
                        self._count_of_tick(what, n, kind)
            elif name == "kv_reads":
                # ``models/common.py`` KV_READS: positions a walked pool's and a
                # ring's decode attention was bounded to and positions live
                # there, by the kind of tick; bytes written into rings
                for what, n in zip(KV_READS, counted):
                    self._rec.count(what, n)
                    if not what.endswith("_written"):
                        self._count_of_tick(what, n, kind)
            elif name == "kv_pass_reads":
                # a looped stack's walks pass by pass (``models/common.py``
                # PASS_READS): each pass's pair, summed over the layers
                for i, n in enumerate(counted):
                    self._rec.count(f"{PASS_READS[i % len(PASS_READS)]}_pass"
                                    f"{i // len(PASS_READS)}_{kind}", n)
            elif name == "moe_group_rows":
                # group-limited routing over held experts: the real rows whose
                # kept groups reach an expert held here, of the real rows routed
                self._rec.count(f"moe_rows_group_kept_{kind}", counted[0])
                self._rec.count(f"moe_rows_group_routed_{kind}", counted[1])
        return tok[:ran]

    def _count_of_tick(self, name: str, n: int, kind: str) -> None:
        """A count the device made of one tick's operands: summed by the
        kind of tick, and kept tick by tick in the ring (a ``count:<name>``
        record with the count for its ``uid`` and the tick's kind): the ticks
        of a few seconds differ from the mean tick by a third, and whoever
        sets a traced slice's kernel time against what its ticks were owed
        needs THOSE ticks' counts."""
        self._rec.count(f"{name}_{kind}", n)
        now = time.perf_counter()
        self._rec.record(f"count:{name}", now, now, n, self._source, kind)

    def _count_state(self, write_pos: np.ndarray, fed: Optional[int] = None,
                     computed: Optional[int] = None) -> None:
        """One target pass of a model with recurrent state: the state of
        every slot the program ran (``write_pos``: one entry each) is read and
        written once, live or parked; a slot that writes
        at position 0 was zeroed first (a join); a prefill tick also says
        how many of the positions its scan ran over were real."""
        if not self._recurrent:
            return
        self._rec.count("ssm_state_bytes_touched", 2 * len(write_pos) * self._state_bytes)
        self._rec.count("ssm_state_resets", int((write_pos == 0).sum()))
        if computed is not None:
            self._rec.count("ssm_positions_fed", fed)
            self._rec.count("ssm_positions_computed", computed)

    def _count_loop(self, kind: str) -> None:
        """One tick of a looped stack: the passes it ran, by the kind of tick,
        and the weight bytes a decode tick streams for them (the stack once a
        pass, the head once: the host's reckoning from the served tree)."""
        if self._loop_passes == 1:
            return
        self._rec.count(f"loop_passes_run_{kind}", self._loop_passes)
        if kind == "decode":
            self._rec.count("loop_weight_bytes_streamed", self._loop_stream_bytes)

    def _count_moe_rows(self, fed: int, computed: int) -> None:
        """One target forward pass over ``computed`` positions, ``fed`` of
        them real: rows routed to experts against rows the expert matmuls
        run over (parked slots, chunk padding and the buffer's own)."""
        if self._moe_rows is None:
            return
        per_position, rows = self._moe_rows(computed)
        if rows:
            self._rec.count("moe_rows_routed", fed * per_position)
            self._rec.count("moe_rows_computed", rows)

    def _count_kv_write(self, write_pos: np.ndarray, length: int) -> None:
        """One target pass writes ``length`` tokens at each live slot's
        ``write_pos``: positions one pool leaf is handed against positions
        the write rewrites there (whole windows, ``slot_pool_append``: the
        same ones whether its kernel or its loop writes them).
        The ratio is what writing in place costs; a write that relaid the
        pool would touch slots x capacity every tick."""
        live = write_pos[write_pos < self.capacity]
        self._rec.count("kv_positions_written",
                        int(np.minimum(length, self.capacity - live).sum()))
        self._rec.count("kv_window_positions_touched",
                        slot_pool_positions_touched(live, length, self.capacity))

    def _phase(self, name: str, marks: int = 0):
        """A host phase of the tick in progress: a child span of ``tick``
        (or, before the first tick, of ``scheduler_init`` and ``warmup``)."""
        return self._rec.span(name, self._tick_no, self._source, marks)

    def _launch(self, kind: str):
        """The span around a tick's jitted call alone, inside ``dispatch``:
        its wall time beside its CPU time, by the kind of program."""
        launch = self._phase("launch", trace.CPU)
        launch.kind = kind
        return launch

    def _probe(self, at: float, phase: str) -> None:
        """One look at the program in flight (``is_ready``: no wait, no
        transfer) at time ``at``, the close of ``phase``: the first look of a
        tick that finds its tokens made is kept. From then on the device has
        nothing queued until the tick's own program reaches it."""
        flight = self._inflight
        if flight is None:
            return
        if self._dry is None and flight.tok.is_ready():
            self._dry = (at, self._probed, phase)
        self._probed = at

    def _count_dry(self) -> None:
        """The program in flight had ended before the launch of the one
        dispatched behind it returned (the last look): the device ran dry
        under the host, for at least the time since the look that found it
        ended (one ``device_dry`` record beside the tick, whose ``kind`` is the
        phase that ended with that look), at most since the look before it."""
        (seen, before, phase), launched = self._dry, self._probed
        self._rec.count("ticks_device_dry")
        self._rec.count(f"ticks_device_dry_in_{phase}")
        self._rec.count("device_dry_us_min", int((launched - seen) * 1e6))
        self._rec.count("device_dry_us_max", int((launched - before) * 1e6))
        self._rec.record("device_dry", seen, launched, self._tick_no, self._source, phase)

    # ------------------------------------------------------------------
    def warmup(self) -> None:
        """Compile every program this scheduler can ever run, off the
        clock: one call each against fully-parked caches, so every KV
        write drops out of bounds and the outputs are garbage to discard.
        A latency-under-load run must not charge a mid-serve request for
        XLA compile time — and a warm *request* cannot reliably reach the
        rare-path programs (the drafter's refeed verify only runs when
        some slot accepts all k drafts). Touches no request accounting,
        no histograms, and not the sampling rng stream. Each call is one
        ``program`` span: the compile records JAX's events become fall
        under it, and what is left of it is the program's first dispatch."""
        with self._rec.span("warmup", source=self._source, marks=trace.WARMS | trace.TOTAL):
            self._warmup()

    def _warmup(self) -> None:
        parked = np.full(self.slots, self.capacity, np.int32)
        rng = ((jax.random.PRNGKey(0),) if self.config.do_sample else ())
        # host arrays, as every tick hands them over: what a jitted call is
        # given is part of the key it caches its program under
        ids = np.zeros((self.slots, self.config.prefill_chunk), np.int32)
        last_idx = np.zeros(self.slots, np.int32)
        block = np.zeros((self.slots, self.spec_k + 1), np.int32)
        # a rung's operands are the first ``n`` of each, behind the slots it
        # runs: a program a rung, all behind one jitted function a kind
        rungs = [("prefill_rung", (np.arange(n, dtype=np.int32), parked[:n], ids[:n],
                                   last_idx[:n])) for n in self._rungs[:-1]]
        decode_calls = [("decode", (parked,) + rng)] + [
            ("decode_rung", (np.arange(n, dtype=np.int32), parked[:n]) + rng)
            for n in self._decode_rungs[:-1]]
        # a spec-mode scheduler never runs the target's plain decode
        # (step() always spec-ticks) — don't pay its compile
        target_calls = ([("prefill", (parked, ids, last_idx) + rng)]
                        + [(name, args + rng) for name, args in rungs]
                        + ([("verify", (parked, block))] if self.spec_k
                           else decode_calls))
        per_role = [("", self.fns, "_cache", self._serve_params, target_calls)]
        if self._drafter is not None:
            # the draft loop feeds decode a mesh-committed token (see
            # _spec_tick); every other tick input arrives as a host array
            dtok = jax.device_put(np.zeros(self.slots, np.int32), self._placement)  # graft-lint: waive R008 warmup operand placement parity w/ the draft loop, never donated
            per_role.append(("drafter_", self.dfns, "_drafter_cache", self._drafter[1],
                             [("prefill", (parked, ids, last_idx) + rng)]
                             + [(name, args + rng) for name, args in rungs] +
                             [("decode", (parked,) + rng, {"tokens": dtok}),
                              ("verify", (parked, block))]))
        for role, fns, cache_attr, params, calls in per_role:
            for name, args, *by_name in calls:
                if name in fns:
                    with self._phase("program") as program:
                        program.kind = role + name
                        cache, _ = fns[name](params, getattr(self, cache_attr), *args,
                                             **(by_name[0] if by_name else {}))
                    setattr(self, cache_attr, cache)
                    # the host arrays its tick hands it, each copied over by the call
                    self._rec.gauge(f"program_host_operands_{role}{name}",
                                    sum(isinstance(a, np.ndarray) for a in args))

    # ------------------------------------------------------------------
    # submission / admission
    # ------------------------------------------------------------------
    def submit(self, request: Request) -> Request:
        return self.queue.submit(request)

    @property
    def in_flight(self) -> List[Request]:
        """The requests that hold a slot. One whose last token is on its way
        has handed its slot on and is not ``finished`` yet: see :attr:`busy`."""
        return [r for r in self._slot_req if r is not None]

    @property
    def busy(self) -> bool:
        """Whether a step has anything to do: a request waits or holds a
        slot, or a program's tokens are still to be read. What a loop around
        :meth:`step` asks, so that it reads the last program too."""
        return self._inflight is not None or bool(self.in_flight) or len(self.queue) > 0

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _admit(self) -> int:
        free = self._free_slots()
        admitted = self.queue.admit(len(free))
        now = self.clock() if admitted else None
        for slot, req in zip(free, admitted):
            self._slot_req[slot] = req
            req.admit_time = now
            self._rec.record("queue_wait", req.arrival_time, now,
                             req.request_id, self._source)
            # graft-prefix-cache: the reservation may have matched an
            # indexed prefix — restore its KV rows into the slot and
            # start prefill AFTER them, so the tick only pays for the
            # uncached tail (the match always leaves >= 1 prompt token
            # so the tail's last position samples the first new token)
            cached = 0
            match = self.pool.take_match(req.request_id)
            if match is not None and match.cached_tokens:
                self._restore_prefix(slot, match)
                cached = match.cached_tokens
            self._lengths[slot] = self._fed[slot] = cached
            self._sent[slot] = 0
            req.state = PREFILL
            req.prefill_pos = cached
            req.cached_prefix_tokens = cached
        return len(admitted)

    # -- prefix cache (graft-prefix-cache) -----------------------------
    def _kv_rows(self, cache, slot: int, start: int, stop: int) -> Dict[str, np.ndarray]:
        """Host copies of rows ``[start:stop)`` of one slot's KV leaves —
        the publish payload. Reads through the whole-leaf ``device_get``
        (zero-copy on the CPU backend — the migration exporter's lesson)
        and copies ONLY the requested rows: an eager device-side slice
        would compile a fresh XLA program per (start, stop) offset, one
        per publishing request. A copy, because a view would alias the
        device buffer the next donated decode step frees."""
        out: Dict[str, np.ndarray] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
            name = _leaf_name(path)
            if name in POOL_LEAVES or name.endswith("_scale"):
                host = np.asarray(jax.device_get(leaf))
                out[jax.tree_util.keystr(path)] = slot_pool_rows(host, slot, start, stop)
        return out

    def _restore_prefix(self, slot: int, match) -> None:
        """Write a :class:`PrefixMatch`'s payload rows into ``slot`` for
        every cache role (target + drafter when speculating): full-block
        payloads concatenate, the partial block contributes its first
        ``partial_tokens`` rows (the COW copy — the shared source block's
        payload is read, never written). Payload rows restore through the
        migration writer (``slot_pool_set_rows``) so the buffers stay
        XLA-owned on the existing placement."""
        self.settle("prefix")
        roles = [("target", "_cache")]
        if self._drafter is not None:
            roles.append(("drafter", "_drafter_cache"))
        for role, attr in roles:
            parts: Dict[str, list] = {}
            for payload in match.payloads + (
                    [match.partial_payload] if match.partial_tokens else []):
                if not isinstance(payload, dict) or role not in payload:
                    raise MigrationError(
                        f"prefix-cache payload missing {role!r} KV rows — "
                        f"the pool indexed a block this scheduler cannot "
                        f"restore")
                rows = payload[role]
                is_partial = payload is match.partial_payload \
                    and match.partial_tokens
                for key, arr in rows.items():
                    part = arr[:match.partial_tokens] if is_partial else arr
                    parts.setdefault(key, []).append(part)
            leaves = {k: (np.concatenate(v, axis=0) if len(v) > 1 else v[0])
                      for k, v in parts.items()}
            setattr(self, attr, self._restore_slot_kv(
                getattr(self, attr), slot, leaves, match.cached_tokens))

    def _publish_prefix(self, slot: int, req: Request) -> None:
        """Index ``req``'s committed full blocks (prompt at the
        PREFILL->ACTIVE transition, prompt + generated output at
        retirement — multi-turn conversations re-match their own
        history). The pool calls ``fetch`` only for blocks not already
        hashed, so shared prefixes publish their KV rows exactly once."""
        if self.prefix_cache != "on":
            return
        with self._phase("publish"):
            committed = int(self._lengths[slot])
            if committed < self.pool.block_size:
                return
            tokens = np.concatenate([
                np.asarray(req.prompt, np.int64),
                np.asarray(req.output, np.int64)])[:committed]

            # ONE device_get per leaf per publish, host-sliced per block:
            # per-block device slices would compile a fresh XLA program per
            # (start, stop) offset and dominate the tick under load. Lazy and
            # tail-only — the pool walks blocks in order and calls fetch only
            # for blocks not yet indexed, so the first call's ``start`` is the
            # first new row: a finish-time publish whose prompt blocks are
            # already shared transfers just the output tail (or, when every
            # full block is already indexed, nothing at all)
            full: dict = {}

            def fetch(start: int, stop: int) -> dict:
                if not full:
                    full["base"] = start
                    full["target"] = self._kv_rows(self._cache, slot,
                                                   start, committed)
                    if self._drafter is not None:
                        full["drafter"] = self._kv_rows(self._drafter_cache,
                                                        slot, start, committed)
                base = full["base"]
                return {role: {k: arr[start - base:stop - base]
                               for k, arr in rows.items()}
                        for role, rows in full.items() if role != "base"}

            self.pool.publish(req.request_id, tokens, fetch=fetch)

    # ------------------------------------------------------------------
    # tick
    # ------------------------------------------------------------------
    def step(self, admit: bool = True) -> str:
        """One scheduler tick: admit, build and dispatch the next program,
        then read back and commit the one that was in flight. Returns the
        kind of the program it finished (``prefill`` | ``decode`` | ``spec``);
        of the one it dispatched where none was in flight; ``idle`` where
        there was neither."""
        self._tick_no = step_no = self._tick_no + 1
        if self.telemetry is not None:
            self.telemetry.begin_step(step_no)
        with self._rec.span("tick", step_no, self._source, trace.UNIT) as tick:
            # the program in flight began when the one before it was read, a
            # tick ago: taken as not ended yet where this tick starts
            self._probed, self._dry = tick.start, None
            with self._phase("admit"):
                if admit:
                    self._admit()
            held = [i for i, r in enumerate(self._slot_req) if r is not None]
            prefilling = [i for i in held if self._fed[i] < self._slot_req[i].prompt_len]
            active = [i for i in held if self._fed[i] >= self._slot_req[i].prompt_len]
            tick.kind = kind = trace.IDLE
            program = None
            if prefilling and (not active or self._decode_ticks_since_prefill
                               >= self.config.prefill_interleave):
                tick.kind = kind = "prefill"     # before the work: a compile inside names it
                program = self._prefill_tick(prefilling)
                self._decode_ticks_since_prefill = 0
            elif active:
                tick.kind = kind = "spec" if self.spec_k else "decode"
                if self.spec_k:
                    self._spec_tick(active)
                else:
                    program = self._decode_tick(active)
                self._decode_ticks_since_prefill += 1
            before, self._inflight = self._inflight, program
            if kind != "idle" or before is None:    # a program, or a step with nothing to do
                self.ticks[kind] += 1
            if before is not None:
                # the tick that ends here is the program's that is read here, and
                # takes as long as the ticks of its kind that read as many rows
                tick.kind = kind = before.kind
                tick.like = before.ran
                self._commit(before)
            if program is not None:
                if self._serial:
                    self.settle(self._serial)
                # the slots of the requests whose last token is on its way
                for slot, req in program.ending:
                    self._release(slot, req)
            if kind != "idle" and self._serve_t0 is None:
                self._serve_t0 = self.clock()
            with self._phase("heartbeat"):
                self._touch_serving_heartbeat(step_no)
        if self._dry is not None:
            self._count_dry()
        if self.telemetry is not None:
            self.telemetry.end_step(step_no)
            every = self.config.tick_telemetry_every
            if every and step_no % every == 0:
                # the fleet router/autoscaler input signals, landed as a
                # schema'd JSONL event (events.SERVE_EVENT_SCHEMAS);
                # buffered — the window flush syncs, not every tick
                self.telemetry.emit("serve_tick", flush=False,
                                    tick=step_no, kind=kind, **self.signals())
        return kind

    def settle(self, reason: str = "drain") -> Optional[str]:
        """Read back and commit the program in flight, if there is one,
        before its time (counted, by ``reason``); returns its kind. For
        whoever reads or replaces what a tick reads (the cache, the served
        weights, a request straight after a step) and for the end of a loop:
        after it the requests' fields and the scheduler's agree."""
        program, self._inflight = self._inflight, None
        if program is None:
            return None
        self._rec.count("ticks_settled")
        self._rec.count(f"ticks_settled_{reason}")
        self._commit(program)
        return program.kind

    # ------------------------------------------------------------------
    # load signals (graft-fleet: the router/autoscaler currency)
    # ------------------------------------------------------------------
    def signals(self) -> dict:
        """The per-tick load signals ``stats()`` always computed but never
        published: queue depth, in-flight slots, TTFT p50/p99, BlockPool
        occupancy/fragmentation. This exact dict is (a) the ``serve_tick``
        telemetry event body, (b) the replica's ``tick`` protocol message
        to the fleet router, and (c) the autoscaler's decision input."""
        ttft = self.ttft_hist
        return {
            "queue_depth": len(self.queue),
            "in_flight": len(self.in_flight),
            "slots": self.slots,
            "free_slots": len(self._free_slots()),
            "finished": len(self.finished),
            "ttft_p50": ttft.percentile(50) if ttft.count else None,
            "ttft_p99": ttft.percentile(99) if ttft.count else None,
            "pool_free_blocks": self.pool.free_blocks,
            "pool_fragmentation_tokens": self.pool.fragmentation_tokens(),
            "achieved_tok_s": self._achieved_tok_s(),
            # graft-prefix-cache evidence (schema'd serve_tick fields) +
            # the affinity advertisement the fleet router matches against
            "prefix_cache_hit_rate": self.pool.prefix_hit_rate(),
            "cached_blocks": self.pool.cached_blocks,
            "prefix_hot": self.pool.hot_prefixes(),
            "prefix_block_size": self.pool.block_size,
            # graft-rlhf rollout evidence (schema'd serve_tick fields)
            "rollout_experience": self.rollout_experience,
            "learner_steps_overlapped": self.learner_steps_overlapped,
            "weight_sync_generation": self.weight_sync_generation,
        }

    def _achieved_tok_s(self) -> Optional[float]:
        """Run-to-date generated tokens per wall second since the first
        non-idle tick (finished + in-flight outputs) — the measured side
        graft-calibrate fits against the ``serve_decode`` static price the
        fleet worker stamps. ``None`` until the replica has both tokens
        and wall time, so a cold replica never reports a fake zero rate."""
        if self._serve_t0 is None:
            return None
        wall = self.clock() - self._serve_t0
        tokens = (sum(len(r.output) for r in self.finished)
                  + sum(len(r.output) for r in self.in_flight))
        if wall <= 0 or not tokens:
            return None
        return tokens / wall

    def serving_static_price(self) -> dict:
        """Static price of the steady-state serving program (the verify
        pass under speculation, plain decode otherwise) — jaxpr-only, the
        exact dict ``static_price_from_jaxpr`` gives a train step, so the
        fleet worker can stamp it into its telemetry run header and
        serving programs enter the graft-calibrate fit in the same units
        as training steps. Degrades to an ``{"error": ...}`` stamp (the
        engine run-header contract) rather than refusing to serve."""
        try:
            from deepspeed_tpu.analysis.cost import static_price_from_jaxpr
            name = "verify" if self.spec_k else "decode"
            write_pos = jax.numpy.zeros((self.slots,), jax.numpy.int32)
            if self.spec_k:
                args = (write_pos, jax.numpy.zeros((self.slots, self.spec_k + 1),
                                                   jax.numpy.int32))
            else:
                args = (write_pos,)
                if self.config.do_sample:
                    args += (jax.random.PRNGKey(0),)
            closed = jax.make_jaxpr(self.fns[name])(
                self._serve_params, self._cache, *args)
            return static_price_from_jaxpr(closed, name=f"serve_{name}",
                                           kind="serve_decode")
        except Exception as e:  # pricing must never take the replica down
            return {"error": f"{type(e).__name__}: {str(e)[:200]}"}

    # ------------------------------------------------------------------
    # graft-rlhf: weight hot-swap seam
    # ------------------------------------------------------------------
    def swap_served_params(self, params, expected_digest: Optional[str] = None,
                           generation: Optional[int] = None,
                           evidence: Optional[dict] = None) -> None:
        """Hot-swap the served params between decode ticks (graft-rlhf
        weight sync). Every serving program takes ``self._serve_params``
        explicitly per call, so swapping the attribute swaps the weights
        the NEXT tick serves with zero recompile — KV already written
        stays valid (it was computed under the generation that wrote it;
        in-flight requests finish on the new weights, which is the
        standard in-flight RLHF staleness contract).

        The new tree must match the served tree exactly (structure,
        shapes, dtypes) — a drifted learner tree is refused loudly, not
        served. When ``expected_digest`` is given the placed params are
        re-digested and verified against what the learner published, so
        generation N's served weights are proven bit-identical to the
        sync evidence. Under a quantized weight view (``weight_dtype !=
        "fp"``) the fp params are re-encoded through ``_quant_view`` and
        digest verification is refused (the re-encode is lossy by
        design — the caller must not expect fp-bit identity).

        The program in flight, if any, is read first: it was dispatched
        under the weights it is accounted to."""
        self.settle("swap")
        if self.weight_dtype != "fp":
            if expected_digest is not None:
                raise ValueError(
                    f"digest verification is meaningless under a quantized "
                    f"weight view (wq={self.weight_dtype}): the served "
                    f"params are a lossy re-encode of what the learner "
                    f"published — pass expected_digest=None")
            _, new_params = _quant_view(self.engine.module, params,
                                        self.weight_dtype,
                                        self.config.weight_group_size)
        else:
            new_params = params

        old_leaves, old_def = jax.tree_util.tree_flatten_with_path(
            self._serve_params)
        new_leaves, new_def = jax.tree_util.tree_flatten_with_path(new_params)
        if old_def != new_def:
            raise ValueError(
                "swap_served_params: new tree structure differs from the "
                "served tree — the learner's params drifted from what this "
                "scheduler compiled against")
        problems = []
        for (path, old), (_, new) in zip(old_leaves, new_leaves):
            if getattr(old, "shape", None) != getattr(new, "shape", None) \
                    or getattr(old, "dtype", None) != getattr(new, "dtype", None):
                problems.append(
                    f"{jax.tree_util.keystr(path)}: served "
                    f"{getattr(old, 'shape', '?')}/{getattr(old, 'dtype', '?')}"
                    f" vs new {getattr(new, 'shape', '?')}/"
                    f"{getattr(new, 'dtype', '?')}")
        if problems:
            raise ValueError("swap_served_params: leaf drift — "
                             + "; ".join(problems[:5]))

        placed = jax.tree.map(
            lambda v, old: jax.device_put(v, old.sharding),  # graft-lint: waive R008 jax-owned served weights, never donated
            new_params, self._serve_params)
        jax.block_until_ready(placed)
        digest_verified = False
        if expected_digest is not None:
            from deepspeed_tpu.runtime.rlhf.sync import params_digest
            got = params_digest(placed)
            if got != expected_digest:
                raise ValueError(
                    f"swap_served_params: digest mismatch after placement — "
                    f"learner published {expected_digest[:16]}… but the "
                    f"placed params digest to {got[:16]}…")
            digest_verified = True
        self._serve_params = placed
        self.weight_sync_generation = (generation if generation is not None
                                       else self.weight_sync_generation + 1)
        self.last_weight_sync = dict(evidence or {},
                                     digest_verified=digest_verified)
        if self.telemetry is not None:
            ev = evidence or {}
            self.telemetry.emit(
                "rlhf_weight_sync", generation=self.weight_sync_generation,
                gather_bytes=ev.get("gather_bytes"),
                total_bytes=ev.get("total_bytes"),
                digest_verified=digest_verified,
                in_flight=len(self.in_flight))

    def _touch_serving_heartbeat(self, tick: int) -> None:
        """Refresh the PR-13 supervisor heartbeat with a serving role
        block (slots in flight, queue depth, last tick monotonic) at
        ``heartbeat_interval`` cadence. A no-op outside a supervised
        process (no ``DS_ELASTIC_HEARTBEAT_FILE``) — the env check is the
        first thing ``touch_heartbeat`` does."""
        import os
        from deepspeed_tpu.elasticity.elastic_agent import (HEARTBEAT_ENV,
                                                            touch_heartbeat)
        if not os.environ.get(HEARTBEAT_ENV):
            return
        touch_heartbeat(
            min_interval=self.config.heartbeat_interval,
            payload={"role": "serving", "tick": tick,
                     "slots_in_flight": len(self.in_flight),
                     "queue_depth": len(self.queue),
                     "last_tick_monotonic": time.monotonic()})

    # -- prefill -------------------------------------------------------
    def _rung_rows(self, slots: List[int], rungs: Tuple[int, ...]) -> np.ndarray:
        """The slots a tick's program runs, one a sequence: the smallest of
        ``rungs`` (the ladder of the tick's kind) that holds ``slots``. The
        whole rung is every slot in its place. A smaller one is the fed slots
        and then, to fill it, other slots, which the tick parks at the
        sentinel as the whole program parks every slot it does not feed:
        distinct, so that no two rows of a write-back are one slot."""
        n = next(r for r in rungs if r >= len(slots))
        if n == self.slots:
            return np.arange(self.slots, dtype=np.int32)
        fed = set(slots)
        others = [i for i in range(self.slots) if i not in fed]
        return np.asarray(list(slots) + others[:n - len(slots)], np.int32)

    def _prefill_tick(self, slots: List[int]) -> _Program:
        C = self.config.prefill_chunk
        with self._phase("build_inputs") as build:
            self._probe(build.start, "admit")
            rows = self._rung_rows(slots, self._rungs)
            n = len(rows)
            row_of = {int(i): j for j, i in enumerate(rows)}
            ids = np.zeros((n, C), np.int32)
            last_idx = np.full(n, C - 1, np.int32)
            write_pos = np.full(n, self.capacity, np.int64)
            rems: Dict[int, int] = {}
            for i in slots:
                req, j = self._slot_req[i], row_of[i]
                chunk = req.prompt[self._fed[i]:self._fed[i] + C]
                rems[i] = rem = len(chunk)
                ids[j, :rem] = chunk
                last_idx[j] = rem - 1
                write_pos[j] = self._lengths[i]
        # ``_computed`` is the cell's whole shape whatever rung ran (the
        # benchmark counts prefill ticks by it); ``_run`` is what the tick's
        # program computed, and fed over run is the rung's fill
        fed = sum(rems.values())
        self._rec.count("prefill_positions_fed", fed)
        self._rec.count("prefill_positions_computed", self.slots * C)
        self._rec.count("prefill_positions_run", n * C)
        self._rec.count("prefill_slots_fed", len(slots))
        self._rec.count(f"prefill_ticks_rung_{n}")
        self._count_moe_rows(fed, n * C)
        self._count_kv_write(write_pos, C)
        self._count_state(write_pos, fed, n * C)
        self._count_loop("prefill")
        with self._phase("stamp"):
            inputs = (write_pos.astype(np.int32), ids, last_idx)
            name = "prefill"
            if n < self.slots:
                name, inputs = "prefill_rung", (rows,) + inputs
        with self._phase("dispatch") as dispatch:
            self._probe(dispatch.start, "build_inputs")
            if self.config.do_sample:
                self._rng, key = jax.random.split(self._rng)
                inputs += (key,)
            with self._launch("prefill") as launch:
                self._cache, tok = self.fns[name](self._serve_params, self._cache, *inputs)
                if self._drafter is not None:  # speculation is greedy: no rng operand
                    self._drafter_cache, _ = self.dfns[name](
                        self._drafter[1], self._drafter_cache, *inputs)
            self._probe(launch.end, "launch")
            # the prompt that completes here samples the request's first token
            with self._phase("account"):
                return self._dispatched("prefill", n, tok, [
                    (i, self._slot_req[i], row_of[i], rems[i],
                     self._fed[i] + rems[i] >= self._slot_req[i].prompt_len) for i in slots])

    # -- plain decode --------------------------------------------------
    def _decode_tick(self, slots: List[int]) -> _Program:
        with self._phase("build_inputs") as build:
            self._probe(build.start, "admit")
            rows = self._rung_rows(slots, self._decode_rungs)
            n = len(rows)
            row_of = {int(i): j for j, i in enumerate(rows)}
            write_pos = np.full(n, self.capacity, np.int64)
            write_pos[[row_of[i] for i in slots]] = self._lengths[slots]
        # ``_computed`` is the cell's whole shape whatever rung ran (the
        # benchmark counts decode ticks by it); ``_run`` is what the tick's
        # program computed
        self._rec.count("decode_slots_fed", len(slots))
        self._rec.count("decode_slots_computed", self.slots)
        self._rec.count("decode_slots_run", n)
        self._rec.count(f"decode_ticks_rung_{n}")
        self._count_moe_rows(len(slots), n)
        self._count_kv_write(write_pos, 1)
        self._count_state(write_pos)
        self._count_loop("decode")
        with self._phase("stamp"):
            inputs = (write_pos.astype(np.int32),)
            name = "decode"
            if n < self.slots:
                name, inputs = "decode_rung", (rows,) + inputs
        with self._phase("dispatch") as dispatch:
            self._probe(dispatch.start, "build_inputs")
            if self.config.do_sample:
                self._rng, key = jax.random.split(self._rng)
                inputs += (key,)
            # each slot's token is the cache's own (``programs.TOKEN_LEAF``)
            with self._launch("decode") as launch:
                self._cache, tok = self.fns[name](self._serve_params, self._cache, *inputs)
            self._probe(launch.end, "launch")
            with self._phase("account"):
                return self._dispatched("decode", n, tok, [
                    (i, self._slot_req[i], row_of[i], 0, True) for i in slots])

    def _dispatched(self, kind: str, ran: int, tok, rows: list) -> _Program:
        """What the host knows of a program the moment it is dispatched,
        by counting: a row advances its slot by the prompt tokens it fed (a
        decode row by the one token it fed) and, where it samples the
        request's next token, by one token sent; a request whose count of
        tokens is then full ends with this program."""
        self._rec.count("ticks_dispatched")
        if self._inflight is not None:
            self._rec.count("ticks_dispatched_ahead")
        ending = []
        for slot, req, _, fed, samples in rows:
            wrote = fed if kind == "prefill" else 1
            self._fed[slot] += fed
            self._lengths[slot] += wrote
            self.pool.advance(req.request_id, wrote)
            if samples:
                self._sent[slot] += 1
                if self._sent[slot] >= req.max_new_tokens:
                    ending.append((slot, req))
        return _Program(kind, ran, tok, rows, ending)

    def _commit(self, program: _Program) -> None:
        """The blocking read-back of a program's tokens, and what they tell:
        each request's progress as it is now known, its token stamped at the
        read-back's return, whether it ended."""
        with self._phase("device_wait"):
            tok = self._read_back(program.tok, program.kind)
        with self._phase("commit"):
            now = self.clock()
            for slot, req, row, fed, samples in program.rows:
                if req.state == FINISHED:
                    # it ended on its EOS a program ago: this row ran for nothing
                    self._rec.count("slot_ticks_discarded")
                    continue
                req.prefill_pos += fed
                if not samples:
                    continue
                if req.state == PREFILL:
                    # prompt complete: the chunk's last-position logits sampled
                    # the FIRST new token — TTFT stops here. The committed
                    # prompt's full blocks enter the hash index now, so the
                    # next same-prefix request skips their prefill entirely
                    self._publish_prefix(slot, req)
                    req.state = ACTIVE
                    if req.admit_time is not None:   # None: migrated in
                        self._rec.record("prefill_wait", req.admit_time, now,
                                         req.request_id, self._source)
                req.record_token(int(tok[row]), now)
                self._maybe_finish(slot, req, now)

    # -- speculative decode --------------------------------------------
    def _spec_tick(self, slots: List[int]) -> None:
        """One speculation round: k drafter steps, one batched target
        verify over the k+1 block, host-side longest-prefix acceptance.
        The drafter re-feeds the verify block only when some slot accepted
        every draft (its own pass never wrote the kth draft's KV)."""
        k = self.spec_k
        d_params = self._drafter[1]
        with self._phase("build_inputs"):
            write_pos = np.full(self.slots, self.capacity, np.int64)
            for i in slots:
                write_pos[i] = self._lengths[i]
            # a slot's next token is the last its request was given
            first = np.zeros(self.slots, np.int32)
            for i in slots:
                first[i] = self._slot_req[i].output[-1]
        # a round is dispatched and read here, whole: the accept loop needs it
        for name in ("ticks_dispatched", "ticks_settled", "ticks_settled_spec"):
            self._rec.count(name)
        self._rec.count("decode_slots_fed", len(slots))
        self._rec.count("decode_slots_computed", self.slots)
        self._count_moe_rows(len(slots) * (k + 1), self.slots * (k + 1))  # the verify pass
        self._count_kv_write(write_pos, k + 1)
        # committed to the mesh placement so iteration 1's input sharding
        # matches iterations 2..k (which feed the previous jit output back);
        # an uncommitted first feed would cost a second decode compile
        with self._phase("stamp"):
            write_pos = write_pos.astype(np.int32)
            self._rec.count("tick_input_puts")
            cur = jax.device_put(first, self._placement)  # graft-lint: waive R008 host token mirror to mesh placement, never donated
        drafts = []
        for j in range(k):
            with self._phase("dispatch"):
                self._drafter_cache, cur = self.dfns["decode"](
                    d_params, self._drafter_cache, write_pos + j, tokens=cur)
            drafts.append(cur)
        with self._phase("device_wait"):
            drafts = np.stack([np.asarray(d) for d in drafts], axis=1)  # [S, k]
        with self._phase("build_inputs"):
            block = np.zeros((self.slots, k + 1), np.int32)
            block[:, 0] = first
            for i in slots:
                block[i, 1:] = drafts[i]
        with self._phase("dispatch"):
            self._cache, greedy = self.fns["verify"](self._serve_params, self._cache,
                                                     write_pos, block)
        with self._phase("device_wait"):
            greedy = np.asarray(greedy)  # [S, k+1] target argmax per position
        refeed = False
        with self._phase("commit"):
            now = self.clock()
            for i in slots:
                req = self._slot_req[i]
                # longest prefix of drafts the target reproduces
                a = 0
                while a < k and drafts[i, a] == greedy[i, a]:
                    a += 1
                emitted = list(drafts[i, :a]) + [greedy[i, a]]
                req.drafted_tokens += k
                req.accepted_tokens += a
                self.drafted_total += k
                self.accepted_total += a
                if a == k:
                    refeed = True  # drafter never wrote d_k's KV — resync below
                # budget/eos truncation
                room = req.max_new_tokens - len(req.output)
                emitted = emitted[:room]
                if req.eos_token_id is not None and req.eos_token_id in emitted:
                    emitted = emitted[:emitted.index(req.eos_token_id) + 1]
                for t in emitted:
                    req.record_token(int(t), now)
                # committed KV: the fed block prefix [last, d_1..d_{m-1}]
                self._lengths[i] += len(emitted)
                self._sent[i] += len(emitted)
                self.pool.advance(req.request_id, len(emitted))
                self._maybe_finish(i, req, now)
        if refeed and any(self._slot_req[i] is not None for i in slots):
            with self._phase("dispatch"):
                self._drafter_cache, _ = self.dfns["verify"](
                    d_params, self._drafter_cache, write_pos, block)

    # ------------------------------------------------------------------
    # live KV migration (graft-fleet)
    # ------------------------------------------------------------------
    def _kv_slot_leaves(self, cache, slot: int, length: int) -> Dict[str, np.ndarray]:
        """Host copies of one slot's committed KV rows — every pool leaf
        (``POOL_LEAVES``: keys and values, or a latent-attention layer's one
        latent pool) plus its kv_quant ``*_scale`` companion, keyed by
        the leaf's ``keystr`` path so target and drafter caches (same leaf
        names, different depths) stay unambiguous. Only ``[:length]`` rows
        travel: everything past the committed prefix is scratch."""
        return self._kv_rows(cache, slot, 0, length)

    def _restore_slot_kv(self, cache, slot: int, leaves: Dict[str, np.ndarray],
                         length: int, token: int = 0):
        """Write migrated KV rows back into one slot of ``cache`` on
        device (``slot_pool_set_rows``), and ``token``, the one the slot is
        fed next (a request still in prefill has none yet). Refuses — ``MigrationError``
        — on a missing/mis-shaped/mis-typed leaf rather than serving a
        half-restored cache.

        The write must stay on device: a ``device_put`` of a host-mutated
        copy is zero-copy on the CPU backend, so the restored leaf would
        alias numpy-owned memory — and the next decode step DONATES the
        cache, handing XLA a buffer it doesn't own to free (heap
        corruption, found the hard way). All leaves update in ONE jitted,
        cache-donating program (``_restore_rows_jit``): prefix-cache
        restores run this per admission, and per-leaf eager ``.at[].set``
        would copy the whole pool once per leaf.

        Donation makes validation ordering load-bearing: callers
        restoring SEVERAL caches (target + drafter) must
        :meth:`_validate_slot_kv` every one of them BEFORE applying the
        first — once a cache is donated, its old buffers are gone, so a
        late validation failure could no longer leave the scheduler
        untouched."""
        flat, treedef, kv_idx, rows = self._validate_slot_kv(cache, leaves,
                                                             length)
        token_idx = next(i for i, (path, _) in enumerate(flat) if _leaf_name(path) == TOKEN_LEAF)
        new_flat = _restore_rows_jit([leaf for _, leaf in flat], rows, np.int32(slot),
                                     np.int32(token), tuple(kv_idx), token_idx)
        return jax.tree_util.tree_unflatten(treedef, new_flat)

    def _validate_slot_kv(self, cache, leaves: Dict[str, np.ndarray],
                          length: int):
        """Check ``leaves`` against ``cache``'s KV geometry WITHOUT
        touching the cache; raises :class:`MigrationError` on a
        missing/mis-shaped/mis-typed leaf. Returns the flattened pieces
        :meth:`_restore_slot_kv` applies."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        kv_idx, rows = [], []
        for i, (path, leaf) in enumerate(flat):
            name = _leaf_name(path)
            if name not in POOL_LEAVES and not name.endswith("_scale"):
                continue
            key = jax.tree_util.keystr(path)
            src = leaves.get(key)
            if src is None:
                raise MigrationError(f"migration bundle missing KV leaf {key}")
            src = np.asarray(src)
            want_shape = (length,) + slot_pool_row_shape(leaf)
            want_dtype = np.dtype(leaf.dtype)
            if src.shape != want_shape or src.dtype != want_dtype:
                raise MigrationError(
                    f"KV leaf {key} mismatch: bundle {src.dtype}{src.shape} "
                    f"vs cache row {want_dtype}{want_shape} — replicas must "
                    f"share kv_quant/geometry to migrate")
            kv_idx.append(i)
            rows.append(np.ascontiguousarray(src))
        return flat, treedef, kv_idx, rows

    def _refuse_recurrent_migration(self) -> None:
        if self._ring:
            raise MigrationError(
                f"live migration over {type(self.module).__name__}: a request's committed "
                f"state travels as cache rows up to its length, and this model's window "
                f"layers keep a ring ({self._ring_names}) whose rows are not at their "
                f"positions: migrating it needs the window's rows in the payload, which is "
                f"not built — drain instead")
        if self._recurrent:
            raise MigrationError(
                f"live migration over {type(self.module).__name__}: a request's committed "
                f"state travels as cache rows up to its length, and this model's recurrent "
                f"state (ssm_state / conv_state) is not rows: migrating it needs the "
                f"state's snapshot in the payload, which is not built — drain instead")

    def export_inflight(self, release: bool = True) -> List[dict]:
        """Serialize every in-flight request — host bookkeeping plus its
        committed per-slot KV — into migration payloads a peer's
        :meth:`admit_migrated` restores bit-exactly.

        Refusal conditions (``MigrationError``, loudly, BEFORE any slot is
        released): sampled decoding (the scheduler-global rng stream is
        not per-request state), or a request outside ``MIGRATABLE_STATES``.
        Greedy decoding is what makes the contract checkable: the migrated
        continuation must be bit-identical to the uninterrupted run.

        ``release=True`` (the SIGTERM path) frees each exported request's
        pool blocks and parks its slot, so the drain loop sees an empty
        scheduler and exits without generating further tokens here.

        The program in flight, if any, is read first: a payload is what a
        request has been GIVEN, and the cache rows behind it."""
        self.settle("export")
        self._refuse_recurrent_migration()
        if self.config.do_sample:
            raise MigrationError(
                "sampled decoding cannot migrate: the sampling rng stream is "
                "scheduler-global, not per-request — drain instead")
        for req in self.in_flight:
            if req.state not in MIGRATABLE_STATES:
                raise MigrationError(f"request {req.request_id} in state "
                                     f"{req.state!r} is not migratable")
        payloads: List[dict] = []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            length = int(self._lengths[slot])
            kv = {"target": self._kv_slot_leaves(self._cache, slot, length)}
            if self._drafter is not None:
                kv["drafter"] = self._kv_slot_leaves(self._drafter_cache,
                                                     slot, length)
            payloads.append({
                "request_id": req.request_id,
                "state": req.state,
                "prompt": np.asarray(req.prompt, np.int32),
                "max_new_tokens": req.max_new_tokens,
                "eos_token_id": req.eos_token_id,
                "arrival_time": req.arrival_time,
                "output": list(req.output),
                "prefill_pos": req.prefill_pos,
                "first_token_time": req.first_token_time,
                "token_times": list(req.token_times),
                "drafted_tokens": req.drafted_tokens,
                "accepted_tokens": req.accepted_tokens,
                "meta": dict(req.meta),
                "length": length,
                "next_token": req.output[-1] if req.output else 0,
                "cached_prefix_tokens": req.cached_prefix_tokens,
                # compat envelope: the importer refuses on any mismatch.
                # prefix_cache rides in it because the KV slices below are
                # already MATERIALIZED (per-slot dense rows — shared
                # blocks export their bytes, not their refs), but the
                # receiving pool's hash envelope must agree before the
                # restored request can publish/re-match over there
                "kv_quant": self.kv_quant,
                "weight_dtype": self.weight_dtype,
                "capacity": self.capacity,
                "spec_k": self.spec_k,
                "prefix_cache": self.prefix_cache,
                "kv": kv,
            })
            if release:
                self.pool.free(req.request_id)
                self._slot_req[slot] = None
                self._lengths[slot] = self.capacity  # park
        return payloads

    def release_inflight(self) -> int:
        """Free every in-flight request's pool blocks and park its slot —
        the post-export half of a migrate-out, split from
        :meth:`export_inflight(release=False)` so a failed bundle save
        leaves the requests still serveable here (drain fallback)."""
        self.settle("export")
        n = 0
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            self.pool.free(req.request_id)
            self._slot_req[slot] = None
            self._lengths[slot] = self.capacity  # park
            n += 1
        return n

    def admit_migrated(self, payload: dict) -> Optional[Request]:
        """Admit one migrated request into a free slot, restoring its KV.

        Returns the (re-identified) local :class:`Request`, or ``None``
        when this replica has no free slot / pool blocks for its worst
        case — a *capacity* refusal the router retries elsewhere, distinct
        from the *compat* refusals (kv_quant / weight dtype / speculation
        geometry mismatch) that raise :class:`MigrationError` because no
        retry can fix them. The request gets a FRESH local id (both
        processes count from 0 — the wire id would collide) with the
        origin id kept in ``meta["migrated_from"]`` for at-most-once
        completion accounting."""
        self.settle("export")
        self._refuse_recurrent_migration()
        for knob in ("kv_quant", "weight_dtype", "spec_k", "capacity",
                     "prefix_cache"):
            if payload.get(knob) != getattr(self, knob):
                raise MigrationError(
                    f"migration compat mismatch on {knob}: bundle "
                    f"{payload.get(knob)!r} vs replica {getattr(self, knob)!r}")
        if payload["state"] not in MIGRATABLE_STATES:
            raise MigrationError(f"bundle request state {payload['state']!r} "
                                 f"is not migratable")
        free = self._free_slots()
        if not free:
            return None
        req = Request(prompt=payload["prompt"],
                      max_new_tokens=payload["max_new_tokens"],
                      eos_token_id=payload["eos_token_id"],
                      arrival_time=payload["arrival_time"])
        if not self.pool.can_allocate(req.total_tokens):
            return None
        req.meta.update(payload.get("meta", {}))
        req.meta["migrated_from"] = payload["request_id"]
        req.state = payload["state"]
        req.output = [int(t) for t in payload["output"]]
        req.prefill_pos = int(payload["prefill_pos"])
        req.first_token_time = payload["first_token_time"]
        req.token_times = list(payload["token_times"])
        req.drafted_tokens = int(payload["drafted_tokens"])
        req.accepted_tokens = int(payload["accepted_tokens"])
        req.cached_prefix_tokens = int(payload.get("cached_prefix_tokens", 0))
        length = int(payload["length"])
        slot = free[0]
        # validate EVERY role before restoring ANY — a MigrationError here
        # must leave the replica untouched (no reserved blocks, no
        # occupied slot, and no cache buffer already donated away by a
        # first restore when a second role's leaves turn out bad)
        self._validate_slot_kv(self._cache, payload["kv"]["target"], length)
        if self._drafter is not None:
            self._validate_slot_kv(self._drafter_cache,
                                   payload["kv"].get("drafter", {}), length)
        token = int(payload["next_token"])
        self._cache = self._restore_slot_kv(self._cache, slot,
                                            payload["kv"]["target"], length, token)
        if self._drafter is not None:
            self._drafter_cache = self._restore_slot_kv(
                self._drafter_cache, slot, payload["kv"]["drafter"], length, token)
        self.pool.reserve(req.request_id, req.total_tokens)
        self.pool.advance(req.request_id, length)
        self._slot_req[slot] = req
        self._lengths[slot] = length
        self._fed[slot] = req.prefill_pos
        self._sent[slot] = len(req.output)
        if self.telemetry is not None:
            self.telemetry.emit("serve_admit_migrated",
                                request_id=req.request_id,
                                migrated_from=payload["request_id"],
                                state=req.state, length=length)
        return req

    # -- retire --------------------------------------------------------
    def _maybe_finish(self, slot: int, req: Request, now: float) -> None:
        """Retire ``req`` if the token just read was its last: by count (its
        slot may have been handed on when that token's program was
        dispatched) or its EOS (found out here)."""
        done = len(req.output) >= req.max_new_tokens
        if req.eos_token_id is not None and req.output and \
                req.output[-1] == req.eos_token_id:
            done = True
        if not done:
            return
        req.state = FINISHED
        req.finish_time = now
        self._release(slot, req)
        self.finished.append(req)
        if req.ttft is not None:
            self.ttft_hist.record(req.ttft)
        for prev, cur in zip(req.token_times, req.token_times[1:]):
            self.tok_hist.record(cur - prev)
        if self.telemetry is not None:
            self.telemetry.emit("serve_request", **req.stats())

    def _release(self, slot: int, req: Request) -> None:
        """Hand ``slot`` on, if ``req`` still holds it: its blocks back to
        the pool, the slot parked."""
        if self._slot_req[slot] is not req:
            return
        # index the full blocks over prompt + output before the free, so
        # the freed blocks park on the cached LRU instead of zeroing —
        # a follow-up turn (prompt = this conversation + more) re-matches
        self._publish_prefix(slot, req)
        self.pool.free(req.request_id)
        self._slot_req[slot] = None
        self._lengths[slot] = self.capacity  # park

    # ------------------------------------------------------------------
    # loops
    # ------------------------------------------------------------------
    def run_until_drained(self, max_ticks: int = 10**9, admit: bool = True) -> int:
        """Tick until queue + slots are empty; returns ticks run."""
        n = 0
        while self.busy and n < max_ticks:
            self.step(admit=admit)
            n += 1
        self.settle()       # ``max_ticks`` may have cut the loop under a program
        return n

    def serve(self, requests=(), guard=None, migrate=None) -> int:
        """Serve ``requests`` to completion under a preemption guard.

        SIGTERM/SIGINT mid-serve triggers the drain contract (reusing
        PR 9's ``runtime/resilience`` signal handling): stop admitting,
        terminally REFUSE everything still queued, FINISH every in-flight
        request, and return ``DEFAULT_PREEMPT_EXIT_CODE`` (143) so a
        supervisor reads preemption, not success. Returns 0 on a normal
        complete drain.

        ``migrate`` (graft-fleet): optional ``migrate(scheduler, signal)
        -> {"migrated": int, "bundle": str}`` hook tried on preemption
        AFTER the queue is refused. On success (the hook exported every
        in-flight request — :meth:`export_inflight` released the slots)
        a ``serve_migrate_out`` event lands and the loop exits without
        generating further tokens here; on :class:`MigrationError` the
        PR-14 drain contract resumes untouched — in-flight requests
        finish locally."""
        from deepspeed_tpu.runtime.resilience.signals import (
            DEFAULT_PREEMPT_EXIT_CODE, PreemptionGuard)
        own_guard = guard is None
        if own_guard:
            guard = PreemptionGuard().install()
        preempted = None
        try:
            for r in requests:
                self.submit(r)
            while self.busy:
                if guard.requested and preempted is None:
                    preempted = guard.consume()
                    refused = self.queue.refuse_all(f"draining on {preempted}")
                    log_dist(f"graft-serve: {preempted} — draining "
                             f"{len(self.in_flight)} in-flight, refused "
                             f"{len(refused)} queued")
                    if self.telemetry is not None:
                        self.telemetry.emit("serve_drain", signal=preempted,
                                            in_flight=len(self.in_flight),
                                            refused=len(refused))
                    if migrate is not None and self.in_flight:
                        try:
                            out = migrate(self, preempted)
                        except MigrationError as e:
                            log_dist(f"graft-serve: migration refused "
                                     f"({e}) — draining instead")
                        else:
                            if self.telemetry is not None:
                                self.telemetry.emit(
                                    "serve_migrate_out", signal=preempted,
                                    migrated=int(out.get("migrated", 0)),
                                    bundle=str(out.get("bundle", "")))
                            continue  # slots released — loop re-checks
                self.step(admit=preempted is None)
        finally:
            self.settle()
            if own_guard:
                guard.uninstall()
        return DEFAULT_PREEMPT_EXIT_CODE if preempted else 0

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Aggregate serving evidence: latency distributions, goodput
        inputs, speculation acceptance, pool accounting, tick mix."""
        done = [r for r in self.finished]
        pool = dict(self.pool.counters())
        # admission-depth units (satellite: visible in every bench row,
        # not just the A/B summary): bytes per KV block and blocks per GB
        # from the measured per-token cache footprint
        block_bytes = max(1, int(round(self._kv_bytes_per_token()
                                       * self.pool.block_size)))
        pool["kv_block_bytes"] = block_bytes
        pool["kv_blocks_per_gb"] = (1 << 30) // block_bytes
        out = {
            "finished": len(done),
            "refused": self.queue.refused,
            "generated_tokens": sum(len(r.output) for r in done),
            "ticks": dict(self.ticks),
            "pool": pool,
            "weight_dtype": self.weight_dtype,
            "kv_quant": self.kv_quant,
            "prefix_cache": self.prefix_cache,
            "cached_prefix_tokens": sum(r.cached_prefix_tokens for r in done),
            "ttft": self.ttft_hist.snapshot() if self.ttft_hist.count else None,
            "per_token": self.tok_hist.snapshot() if self.tok_hist.count else None,
        }
        if self.spec_k:
            out["spec_k"] = self.spec_k
            out["drafted"] = self.drafted_total
            out["accepted"] = self.accepted_total
            out["acceptance_rate"] = (self.accepted_total / self.drafted_total
                                      if self.drafted_total else None)
        if (self.rollout_experience or self.weight_sync_generation
                or self.learner_steps_overlapped):
            # graft-rlhf rollout evidence (present iff this scheduler
            # served an RLHF loop — plain serving stats stay unchanged)
            out["rollout"] = {
                "experience": self.rollout_experience,
                "learner_steps_overlapped": self.learner_steps_overlapped,
                "weight_sync_generation": self.weight_sync_generation,
                "last_weight_sync": self.last_weight_sync,
            }
        return out
