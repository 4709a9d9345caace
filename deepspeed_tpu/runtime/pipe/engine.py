"""PipelineEngine: jitted pipeline-parallel training
(reference ``runtime/pipe/engine.py``: ``PipelineEngine`` :56,
``train_batch`` :286, ``_exec_schedule`` :1295).

TPU-native redesign. The reference interprets a ``TrainSchedule``
instruction stream per process — NCCL p2p sends with a meta handshake
(``engine.py:795``), explicit buffer pools, separate fwd/bwd executors.
Here a schedule is a jitted ``lax.scan`` over ticks with ``ppermute``
neighbor exchange; three schedules are selectable via
``pipeline.schedule``:

* ``1f1b`` (default) — the real thing. Per-tick forward/backward
  interleave with an explicitly managed activation stash: warmup ticks
  run forward-only, steady ticks run one forward AND one backward per
  stage (the backward recomputes its stage body from the stashed
  boundary input and applies a manual ``jax.vjp`` — no autodiff through
  the scan, so liveness is the stash ring, not O(ticks) residuals),
  cooldown ticks drain backwards. The prologue contributes only on
  stage 0 and the LM-head epilogue (loss + its gradient seed) only on
  the last stage; the microbatch loss and the replicated/tied parameter
  gradients are ``psum``'d across ``pipe`` (``ReduceTiedGrads``). Static
  per-stage activation bound: ``2(S-1)`` stash slots + 2 in transit,
  constant in the microbatch count (``schedule.one_f_one_b_table``).
* ``chunked`` — the previous memory-bounded schedule: GPipe-ordered
  differentiable scan in waves of ``chunk_microbatches`` with gradient
  accumulation across waves (one fill/drain bubble per wave, ~2x the
  1F1B activation bound).
* ``gpipe`` — the plain differentiable scan (autodiff residuals grow
  O(M+S); kept as the honest baseline the memory tests pin).

Common structure:

* ``shard_map`` is manual over the ``pipe`` mesh axis only — every other
  axis (data/fsdp/tensor/sequence) stays *automatic*, so ZeRO sharding, TP
  and DP compose inside each stage exactly as in the non-pipelined engine.
* Activations hop stages with ``lax.ppermute`` (``SendActivation``/
  ``RecvActivation``; static shapes, no meta handshake), gradients hop
  back with the reversed permutation (``SendGrad``/``RecvGrad``).
* Convergence matches gradient accumulation (the reference makes the same
  claim for its TrainSchedule, ``schedule.py:189``): microbatches =
  ``gradient_accumulation_steps``.
"""

import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import PIPE_AXIS
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState, _cast_floating, _global_norm
from deepspeed_tpu.runtime.fp16.loss_scaler import has_overflow
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.schedule import TrainSchedule
from deepspeed_tpu.utils.logging import log_dist, logger

#: selectable tick schedules (``pipeline.schedule``)
PIPE_SCHEDULES = ("1f1b", "chunked", "gpipe")


class PipelineEngine(DeepSpeedEngine):
    """Engine for :class:`PipelineModule` models. ``train_batch`` consumes a
    full global batch; microbatches stream through stages."""

    def __init__(self, pipeline: PipelineModule, config, **kwargs):
        self.pipeline = pipeline
        super().__init__(model=pipeline.make_param_module(), config=config, **kwargs)
        if self.topology.pipe_parallel_size != pipeline.num_stages:
            raise ValueError(f"PipelineModule has {pipeline.num_stages} stages but mesh pipe axis "
                             f"is {self.topology.pipe_parallel_size}")
        self.micro_batches = self.config.gradient_accumulation_steps
        if pipeline.loss_fn is not None:
            self.loss_fn = pipeline.loss_fn
        # memory-bounded schedule: run the pipeline in waves of
        # ``chunk_microbatches`` with gradient accumulation across waves.
        # The GPipe-ordered scan's autodiff residuals hold one boundary
        # activation per tick — O(M+S) liveness; 1F1B bounds it at S
        # (reference schedule.py:189). Chunking at C bounds it at C+S-1
        # per wave (C=S → <2x the 1F1B bound, constant in M) at the cost
        # of one extra pipeline fill/drain bubble per wave.
        pipe_cfg = self.config.raw_dict.get("pipeline", {})
        chunk_raw = pipe_cfg.get("chunk_microbatches", 0) or 0
        chunk = int(chunk_raw)
        if chunk != chunk_raw or chunk < 0:
            raise ValueError(f"pipeline.chunk_microbatches must be a non-negative "
                             f"integer, got {chunk_raw!r}")
        if chunk:
            if self.micro_batches % chunk != 0:
                raise ValueError(
                    f"pipeline.chunk_microbatches={chunk} must divide "
                    f"gradient_accumulation_steps={self.micro_batches}")
            if chunk == self.micro_batches:
                chunk = 0  # one wave == the plain schedule
        # pipeline.schedule, else chunked where the config asked for waves
        # (it keeps them), else 1f1b
        sched = pipe_cfg.get("schedule") or ("chunked" if chunk else "1f1b")
        if sched not in PIPE_SCHEDULES:
            raise ValueError(f"pipeline.schedule must be one of {PIPE_SCHEDULES}, "
                             f"got {sched!r}")
        if sched != "chunked" and chunk:
            logger.warning(f"pipeline.chunk_microbatches={chunk} only applies to the "
                           f"chunked schedule; ignored under schedule={sched!r}")
            chunk = 0
        if sched == "chunked" and not chunk:
            # canonical wave size: C=S bounds liveness at <2x the 1F1B
            # bound (module docstring). No silent degrade: if S does not
            # divide M there is no default wave, and falling back to the
            # plain scan would quietly forfeit the memory bound the user
            # opted into — make them pick a chunk size instead.
            s = pipeline.num_stages
            if self.micro_batches % s != 0:
                raise ValueError(
                    f"pipeline.schedule='chunked' needs a wave size: the default "
                    f"C=S={s} does not divide gradient_accumulation_steps="
                    f"{self.micro_batches}; set pipeline.chunk_microbatches to a "
                    f"divisor (or use schedule='1f1b')")
            chunk = s
        self.pipe_schedule = sched
        self.pipe_chunk = chunk
        log_dist(f"PipelineEngine: stages={pipeline.num_stages} "
                 f"micro_batches={self.micro_batches} schedule={sched} "
                 + (f"chunk={chunk} " if chunk else "")
                 + f"(schedule parity: {2 * (self.micro_batches + pipeline.num_stages - 1)} ticks "
                 f"of reference TrainSchedule)")

    # ------------------------------------------------------------------
    def _reference_schedule(self, stage_id: int) -> TrainSchedule:
        """The instruction stream this scan is equivalent to (for tests &
        debugging; reference ``pipe/engine.py:346``)."""
        return TrainSchedule(micro_batches=self.micro_batches,
                             stages=self.pipeline.num_stages,
                             stage_id=stage_id)

    def _pipe_specs(self, tree_specs):
        """shard_map in_specs for the params tree: only the ``pipe``-manual
        dims matter; everything else is automatic."""

        def spec_of(p):
            if PIPE_AXIS in [a for part in p if part for a in (part if isinstance(part, tuple) else (part,))]:
                idx = next(i for i, part in enumerate(p)
                           if part == PIPE_AXIS or (isinstance(part, tuple) and PIPE_AXIS in part))
                parts = [None] * (idx + 1)
                parts[idx] = PIPE_AXIS
                return P(*parts)
            return P()

        return jax.tree.map(spec_of, tree_specs, is_leaf=lambda x: isinstance(x, P))

    def _pipeline_loss_fn(self, micro=None):
        """Build ``loss(params, ids_mb, labels_mb) -> mean loss`` running the
        streaming pipeline under shard_map(manual={'pipe'}). ``micro``
        overrides the microbatch count per invocation (the chunked schedule
        runs waves of ``pipe_chunk`` microbatches)."""
        pipeline = self.pipeline
        mesh = self.mesh
        n_stages = pipeline.num_stages
        layers_per_stage = pipeline.layers_per_stage
        micro = micro or self.micro_batches
        loss_fn = self.loss_fn
        param_specs = self.plan.param_specs

        compute_dtype = self.compute_dtype

        def spmd(params, ids_mb, labels_mb):
            # params["body"] leaves arrive with local leading dim =
            # layers_per_stage; everything else replicated w.r.t. pipe.
            # The compute-dtype cast happens HERE (inside the manual region)
            # so boundary cotangents stay fp32 — casting outside makes XLA
            # psum bf16 cotangents across pipe, which crashes the CPU
            # SPMD partitioner (hlo_instruction.cc "binary opcode copy").
            params = _cast_floating(params, compute_dtype)
            stage = jax.lax.axis_index(PIPE_AXIS)
            is_first = stage == 0
            is_last = stage == n_stages - 1

            body_params = params["body"]
            other = {k: v for k, v in params.items() if k != "body"}

            def stage_body(x):
                def one_block(h, blk):
                    return pipeline.apply_block(blk, h), None
                out, _ = jax.lax.scan(one_block, x, body_params)
                return out
            stage_body = jax.checkpoint(stage_body)

            x0 = pipeline.apply_prologue(other, ids_mb[0])
            act0 = jnp.zeros_like(x0)
            outbuf0 = jnp.zeros((micro,) + x0.shape, x0.dtype)

            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            n_ticks = micro + n_stages - 1

            def tick(carry, t):
                act, outbuf = carry
                mb_idx = jnp.clip(t, 0, micro - 1)
                ids_t = jax.lax.dynamic_index_in_dim(ids_mb, mb_idx, 0, keepdims=False)
                x_in = pipeline.apply_prologue(other, ids_t)
                cur = jnp.where(is_first, x_in, act)
                y = stage_body(cur)
                # LoadMicroBatch/ForwardPass done; collect last-stage output
                out_idx = t - (n_stages - 1)
                valid_out = (out_idx >= 0) & is_last
                outbuf = jax.lax.dynamic_update_index_in_dim(
                    outbuf,
                    jnp.where(valid_out, y,
                              jax.lax.dynamic_index_in_dim(outbuf, jnp.clip(out_idx, 0, micro - 1), 0,
                                                           keepdims=False)),
                    jnp.clip(out_idx, 0, micro - 1), 0)
                # SendActivation/RecvActivation (static shapes: no handshake)
                act_next = jax.lax.ppermute(y, PIPE_AXIS, perm)
                return (act_next, outbuf), None

            (_, outbuf), _ = jax.lax.scan(tick, (act0, outbuf0), jnp.arange(n_ticks))

            # epilogue + loss, vectorized over microbatches (one big MXU-
            # friendly head GEMM instead of per-tick slivers)
            def mb_loss(y, lbl):
                logits = pipeline.apply_epilogue(other, y)
                return loss_fn(logits, {"input_ids": lbl, "labels": lbl})

            losses = jax.vmap(mb_loss)(outbuf, labels_mb)
            local = jnp.mean(losses)
            # only the last stage holds real outputs (_aggregate_total_loss
            # broadcast, reference pipe/engine.py:512)
            return jax.lax.psum(jnp.where(is_last, local, 0.0), PIPE_AXIS)

        in_specs = (self._pipe_specs(param_specs), P(), P())
        return jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=P(),
                             axis_names={PIPE_AXIS}, check_vma=False)

    # ------------------------------------------------------------------
    @property
    def stash_slots(self) -> int:
        """1F1B forward-stash ring size per stage: the forward→backward
        lag is ``2(S-1-s)`` ticks at stage ``s`` (``schedule.
        one_f_one_b_table``), attained at stage 0 — the uniform SPMD
        carry sizes for the worst stage."""
        return max(1, 2 * (self.pipeline.num_stages - 1))

    def _pipeline_1f1b_grads_fn(self):
        """Build ``grads(params, ids_mb, labels_mb, scale) -> (loss, grads)``
        running the combined-tick 1F1B schedule under
        ``shard_map(manual={'pipe'})`` with a MANUAL backward.

        Nothing here is differentiated by ``jax.grad``: each steady/
        cooldown tick recomputes its stage body from the stashed boundary
        input via ``jax.vjp`` and applies the incoming cotangent, so the
        program's liveness is exactly the stash ring plus one tick's
        recompute transient — the property R010 prices. Tick algebra and
        phase structure are specified by ``schedule.one_f_one_b_table``;
        the scan below evaluates the same formulas per stage:

        * fwd micro   ``f = t - s``            (warmup + steady ticks)
        * bwd micro   ``b = t - 2(S-1) + s``   (steady + cooldown ticks)
        * last stage: ``f == b`` — its backward seeds from the epilogue
          loss of the SAME tick's forward input (no stash round-trip).

        Stage-owned prologue/epilogue: the embedding contributes only
        through stage 0 (``is_first`` masks), the LM-head loss/grad
        epilogue only through the last stage (``is_last`` masks), and the
        epilogue appears ONLY in the steady body — warmup and cooldown
        ticks never touch the vocab GEMM. The per-micro loss and the
        replicated (prologue/epilogue/tied) parameter cotangents are
        ``psum``'d over ``pipe`` at the end — ``ReduceTiedGrads`` — which
        is also where the tied embedding's lookup (stage 0) and LM-head
        (last stage) contributions meet.
        """
        pipeline = self.pipeline
        mesh = self.mesh
        n_stages = pipeline.num_stages
        micro = self.micro_batches
        loss_fn = self.loss_fn
        param_specs = self.plan.param_specs
        compute_dtype = self.compute_dtype
        n_slots = self.stash_slots

        def spmd(params, ids_mb, labels_mb, scale):
            # compute-dtype cast inside the manual region, like the
            # differentiable schedules (boundary tensors stay off the
            # automatic-psum path that crashes the CPU SPMD partitioner)
            params = _cast_floating(params, compute_dtype)
            stage = jax.lax.axis_index(PIPE_AXIS)
            is_first = stage == 0
            is_last = stage == n_stages - 1

            body_params = params["body"]
            other = {k: v for k, v in params.items() if k != "body"}

            def block_apply(blk, h):
                return pipeline.apply_block(blk, h)
            # block-granular remat: the backward vjp stashes only per-block
            # boundary activations and recomputes block internals
            block_apply = jax.checkpoint(block_apply)

            def stage_body(bp, x):
                def one_block(h, blk):
                    return block_apply(blk, h), None
                out, _ = jax.lax.scan(one_block, x, bp)
                return out

            def prologue(oth, ids):
                return pipeline.apply_prologue(oth, ids)

            def epi_loss(oth, y, lbl):
                logits = pipeline.apply_epilogue(oth, y)
                return loss_fn(logits, {"input_ids": lbl, "labels": lbl})

            aval = jax.eval_shape(prologue, other, ids_mb[0])
            act0 = jnp.zeros(aval.shape, aval.dtype)
            zeros_f32 = lambda tree: jax.tree.map(  # noqa: E731
                lambda p: jnp.zeros(p.shape, jnp.float32), tree)
            carry0 = {
                "act": act0,                      # activation in transit (fwd)
                "grad": act0,                     # cotangent in transit (bwd)
                "stash": jnp.zeros((n_slots,) + act0.shape, act0.dtype),
                "gbody": zeros_f32(body_params),  # stage-local body grads
                "gother": zeros_f32(other),       # prologue+epilogue grads
                "loss": jnp.zeros((), jnp.float32),
            }
            perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            perm_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]

            def mb_at(arr, m):
                return jax.lax.dynamic_index_in_dim(
                    arr, jnp.clip(m, 0, micro - 1), 0, keepdims=False)

            def fwd_half(carry, t):
                """LoadMicroBatch (stage 0 prologue) / RecvActivation →
                stash write → ForwardPass. Returns (x_f, y_f, stash)."""
                f = t - stage
                valid_f = (f >= 0) & (f < micro)
                x_f = jnp.where(is_first, prologue(other, mb_at(ids_mb, f)),
                                carry["act"])
                # the ring slot f % K frees exactly at this tick on stage 0
                # (read-before-write ordering; schedule.one_f_one_b_table)
                slot_w = jnp.mod(jnp.clip(f, 0, None), n_slots)
                old = jax.lax.dynamic_index_in_dim(carry["stash"], slot_w, 0,
                                                   keepdims=False)
                stash = jax.lax.dynamic_update_index_in_dim(
                    carry["stash"], jnp.where(valid_f, x_f, old), slot_w, 0)
                return x_f, stage_body(body_params, x_f), stash

            def bwd_half(carry, t, x_f=None, with_epilogue=True):
                """RecvGrad / epilogue seed → recompute-vjp BackwardPass →
                masked accumulate. ``x_f`` is the SAME tick's forward
                input (steady ticks): the last stage's backward input,
                bypassing the stash. Returns (g_x, new accumulators)."""
                b = t - 2 * (n_stages - 1) + stage
                valid_b = (b >= 0) & (b < micro)
                slot_r = jnp.mod(b, n_slots)
                x_stash = jax.lax.dynamic_index_in_dim(carry["stash"], slot_r, 0,
                                                       keepdims=False)
                x_b = x_stash if x_f is None else jnp.where(is_last, x_f, x_stash)
                y_b, body_vjp = jax.vjp(stage_body, body_params, x_b)
                if with_epilogue:
                    lbl_b = mb_at(labels_mb, b)
                    loss_b, epi_vjp = jax.vjp(
                        lambda oth, yy: epi_loss(oth, yy, lbl_b), other, y_b)
                    g_oth_epi, g_y_epi = epi_vjp(scale.astype(loss_b.dtype))
                    g_y = jnp.where(is_last, g_y_epi.astype(carry["grad"].dtype),
                                    carry["grad"])
                else:  # cooldown: the last stage drained inside steady
                    g_y = carry["grad"]
                g_bp, g_x = body_vjp(g_y)
                _, pro_vjp = jax.vjp(lambda oth: prologue(oth, mb_at(ids_mb, b)),
                                     other)
                (g_oth_pro,) = pro_vjp(g_x)

                def acc(a, g, m):
                    return jax.tree.map(
                        lambda aa, gg: aa + jnp.where(m, gg.astype(jnp.float32), 0.0),
                        a, g)

                gbody = acc(carry["gbody"], g_bp, valid_b)
                gother = acc(carry["gother"], g_oth_pro, valid_b & is_first)
                loss = carry["loss"]
                if with_epilogue:
                    gother = acc(gother, g_oth_epi, valid_b & is_last)
                    loss = loss + jnp.where(valid_b & is_last,
                                            loss_b.astype(jnp.float32), 0.0)
                return g_x, gbody, gother, loss

            def warmup_tick(carry, t):
                _, y_f, stash = fwd_half(carry, t)
                return dict(carry, act=jax.lax.ppermute(y_f, PIPE_AXIS, perm_fwd),
                            stash=stash), None

            def steady_tick(carry, t):
                x_f, y_f, stash = fwd_half(carry, t)
                g_x, gbody, gother, loss = bwd_half(carry, t, x_f=x_f)
                return {"act": jax.lax.ppermute(y_f, PIPE_AXIS, perm_fwd),
                        "grad": jax.lax.ppermute(g_x, PIPE_AXIS, perm_bwd),
                        "stash": stash, "gbody": gbody, "gother": gother,
                        "loss": loss}, None

            def cooldown_tick(carry, t):
                g_x, gbody, gother, loss = bwd_half(carry, t, with_epilogue=False)
                return dict(carry, grad=jax.lax.ppermute(g_x, PIPE_AXIS, perm_bwd),
                            gbody=gbody, gother=gother, loss=loss), None

            carry, _ = jax.lax.scan(warmup_tick, carry0, jnp.arange(n_stages - 1))
            carry, _ = jax.lax.scan(steady_tick, carry,
                                    jnp.arange(n_stages - 1, micro + n_stages - 1))
            carry, _ = jax.lax.scan(
                cooldown_tick, carry,
                jnp.arange(micro + n_stages - 1, micro + 2 * n_stages - 2))

            # ReduceTiedGrads + _aggregate_total_loss in one place: the
            # replicated prologue/epilogue cotangents and the last-stage
            # loss meet across pipe
            denom = micro * scale
            gother = jax.tree.map(
                lambda g: jax.lax.psum(g, PIPE_AXIS) / denom, carry["gother"])
            gbody = jax.tree.map(lambda g: g / denom, carry["gbody"])
            loss = jax.lax.psum(carry["loss"], PIPE_AXIS) / micro
            grads = dict(gother, body=gbody)
            return loss, grads

        in_specs = (self._pipe_specs(param_specs), P(), P(), P())
        out_specs = (P(), self._pipe_specs(param_specs))
        return jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names={PIPE_AXIS},
                             check_vma=False)

    # ------------------------------------------------------------------
    def _build_step_fns(self):
        cfg = self.config
        clip = cfg.gradient_clipping
        fp16 = self._fp16_mode
        grad_shardings = self.plan.grad_shardings()
        mesh = self.mesh
        sched = self.pipe_schedule
        chunk = self.pipe_chunk
        n_chunks = (self.micro_batches // chunk) if chunk else 1
        # eval is forward-only (no autodiff residuals): it always runs the
        # full-micro differentiable scan, whatever the training schedule
        pipe_loss = (None if sched == "1f1b"
                     else self._pipeline_loss_fn(micro=chunk if chunk else None))
        eval_pipe_loss = (self._pipeline_loss_fn()
                          if (sched == "1f1b" or chunk) else pipe_loss)
        pipe_grads_1f1b = (self._pipeline_1f1b_grads_fn()
                           if sched == "1f1b" else None)
        compute_dtype = self.compute_dtype

        def _split(batch):
            ids = batch["input_ids"] if isinstance(batch, dict) else batch
            labels = batch.get("labels", ids) if isinstance(batch, dict) else ids
            return ids, labels

        def chunk_loss_of(params, ids, labels, scale):
            # dtype cast happens inside the shard_map region (see spmd)
            loss = pipe_loss(params, ids, labels)
            return (loss * scale).astype(jnp.float32), loss

        def loss_of(params, batch, scale):
            return chunk_loss_of(params, *_split(batch), scale)

        def _grads_full(params, batch, scale):
            (_, loss), grads = jax.value_and_grad(loss_of, has_aux=True)(params, batch, scale)
            grads = _cast_floating(grads, jnp.float32)
            return loss, jax.tree.map(lambda g: g / scale, grads)

        def _grads_1f1b(params, batch, scale):
            # manual-vjp schedule: (loss, unscaled mean grads) directly —
            # same contract as _grads_full without differentiating the scan
            return pipe_grads_1f1b(params, *_split(batch), scale)

        def _grads_chunked(params, batch, scale):
            # wave-wise accumulation: value_and_grad completes INSIDE each
            # scan iteration, so autodiff residuals (one boundary activation
            # per tick) live only for one chunk+fill — the memory-bounded
            # schedule standing in for 1F1B's interleave
            ids, labels = _split(batch)
            ids = ids.reshape((n_chunks, chunk) + ids.shape[1:])
            labels = labels.reshape((n_chunks, chunk) + labels.shape[1:])

            def wave(acc, xs):
                i_c, l_c = xs
                (_, loss_c), g = jax.value_and_grad(chunk_loss_of, has_aux=True)(
                    params, i_c, l_c, scale)
                g = _cast_floating(g, jnp.float32)
                return jax.tree.map(jnp.add, acc, g), loss_c

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(wave, zeros, (ids, labels))
            return jnp.mean(losses), jax.tree.map(lambda g: g / (n_chunks * scale), grads)

        grads_of = (_grads_1f1b if sched == "1f1b"
                    else _grads_chunked if chunk else _grads_full)

        def train_step(state: TrainState, batch, rng):
            scale = state.loss_scale.loss_scale if fp16 else jnp.float32(1.0)
            loss, grads = grads_of(state.params, batch, scale)
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)

            gnorm = _global_norm(grads)
            # every dtype mode skips on non-finite grads (a bf16/fp32 inf/nan
            # would silently poison params), matching the base engine
            overflow = has_overflow(grads) if fp16 else ~jnp.isfinite(gnorm)
            if clip > 0:
                factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)

            new_params, new_opt = self._cond_apply_updates(
                overflow, grads, state.opt_state, state.params)
            new_ls = self._ls_update(state.loss_scale, overflow)
            new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt, loss_scale=new_ls)
            metrics = {"loss": loss, "grad_norm": gnorm, "overflow": overflow,
                       "loss_scale": new_ls.loss_scale}
            return new_state, metrics

        self._train_step_fn = jax.jit(
            train_step,
            in_shardings=(self.state_shardings, None, NamedSharding(mesh, P())),
            out_shardings=(self.state_shardings, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

        # fused multi-step dispatch (base-engine train_batches contract),
        # shared jit builder so pipe rungs amortize host dispatch like
        # every other engine
        self._train_steps_fn = self._jit_train_steps(train_step)

        def eval_step(params, batch):
            ids, labels = _split(batch)
            return eval_pipe_loss(params, ids, labels)

        self._eval_step_fn = jax.jit(eval_step,
                                     in_shardings=(self.state_shardings.params, None),
                                     out_shardings=NamedSharding(mesh, P()))
        self._micro_grad_fn = None  # forward/backward shims are not a
        self._apply_grads_fn = None  # pipeline concept (reference also routes
        # everything through train_batch, pipe/engine.py:286)

    # ------------------------------------------------------------------
    def traced_programs(self, example_batch, **kwargs):
        """Base metadata plus the pipeline schedule's static-cost
        contract (graft-audit, analysis/cost.py):

        * ``activation_budget_bytes`` — from ``pipeline.activation_budget_mb``
          (or the ``DS_PIPE_ACT_BUDGET_MB`` env override, lint's seeded-
          regression path: a budget, not a program choice). When declared,
          R010 gates the statically estimated transient peak against it:
          the pre-wired CPU gate for the ROADMAP-2 1F1B refactor's
          ``<=1F1B`` bound. No budget declared = inventoried, not gated.
        * ``collective_signature`` — each tick boundary hops exactly one
          boundary activation forward and one cotangent backward over
          ``ppermute``: 2 ``collective_permute`` per tick. The
          differentiable schedules (gpipe/chunked) carry 2 sites at the
          jaxpr layer (the scan body + its autodiff transpose); the 1F1B
          schedule carries 4 (the steady body holds both directions, the
          warmup body the activation hop, the cooldown body the gradient
          hop). More would mean a second boundary buffer per tick — the
          drift this signature exists to catch.
        """
        programs = super().traced_programs(example_batch, **kwargs)
        metadata = programs["train_step"]["metadata"]
        pipe_cfg = self.config.raw_dict.get("pipeline", {})
        budget_mb = os.environ.get("DS_PIPE_ACT_BUDGET_MB",
                                   pipe_cfg.get("activation_budget_mb"))
        if budget_mb is not None:
            metadata["activation_budget_bytes"] = int(float(budget_mb) * 2**20)
        metadata["pipe_schedule"] = {
            "stages": self.pipeline.num_stages,
            "micro_batches": self.micro_batches,
            "schedule": self.pipe_schedule,
            "chunk_microbatches": self.pipe_chunk,
        }
        if self.pipe_schedule == "1f1b":
            metadata["pipe_schedule"]["stash_slots"] = self.stash_slots
        sig = metadata.setdefault("collective_signature", [])
        if self.pipe_schedule == "1f1b":
            sig.append({"layer": "jaxpr", "kind": "collective_permute", "count": 4,
                        "note": "2 boundary hops per tick boundary (act fwd + "
                                "grad bwd) over 3 phase bodies: warmup holds "
                                "the act hop, steady both, cooldown the grad "
                                "hop"})
        else:
            sig.append({"layer": "jaxpr", "kind": "collective_permute", "count": 2,
                        "note": "one boundary-activation hop per scan tick "
                                "(fwd + transposed bwd share the body)"})
        return programs

    def train_batch(self, batch=None, data_iter=None):
        """Reference ``pipe/engine.py:286``: consume ``micro_batches``
        microbatches, return the aggregated loss."""
        return super().train_batch(batch=batch, data_iter=data_iter)

    def _telemetry_run_extra(self):
        """Pipeline provenance for the telemetry run header: drift ratios
        on a pipe rung are meaningless without the schedule that shaped
        the program (same fields traced_programs stamps for R009/R010)."""
        extra = {"pipe_schedule": {"stages": self.pipeline.num_stages,
                                   "micro_batches": self.micro_batches,
                                   "schedule": self.pipe_schedule,
                                   "chunk_microbatches": self.pipe_chunk}}
        if self.pipe_schedule == "1f1b":
            extra["pipe_schedule"]["stash_slots"] = self.stash_slots
        return extra

    def eval_batch(self, batch):
        """Reference ``pipe/engine.py:363``."""
        self.initialize_state(batch)
        device_batch = self._shard_batch(batch, with_gas_dim=True)
        return self._eval_step_fn(self.state.params, device_batch)

    def forward(self, *a, **k):
        raise RuntimeError("PipelineEngine does not support forward(); use train_batch/eval_batch "
                           "(reference raises the same, pipe/engine.py)")

    backward = forward
    step = forward

    def _example_ids(self, batch):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        if ids.ndim == 3:  # [gas, micro, seq]
            ids = ids[0]
        return jnp.zeros((1, ids.shape[-1]), jnp.int32)

    def _shard_batch(self, batch, with_gas_dim: bool = True):
        # pipeline always consumes the full [micro_batches, mb, ...] layout
        return super()._shard_batch(batch, with_gas_dim=True)
