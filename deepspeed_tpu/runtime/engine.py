"""DeepSpeedEngine — the central training wrapper.

TPU-native redesign of reference ``runtime/engine.py:181``
(``DeepSpeedEngine``). The reference wraps an ``nn.Module`` and drives
forward/backward/step imperatively with autograd hooks; here the whole
optimization step — gradient-accumulation scan, mixed-precision cast,
grad reduction, overflow-checked update — is ONE jitted function whose
input/output shardings encode the ZeRO placement plan
(``runtime/zero/planner.py``). XLA then emits the reduce-scatters /
all-gathers the reference issues by hand (``stage_1_and_2.py:948``,
``stage3.py:1176``) and overlaps them with compute.

API parity:
* ``train_batch(batch)``  — fused fwd+bwd+step over GAS microbatches
  (the preferred path; ≅ ``PipelineEngine.train_batch``).
* ``forward``/``backward``/``step``  — torch-style shims with reference
  GAS-boundary semantics (``engine.py:1709,1850,2051,1936``).
* ``save_checkpoint``/``load_checkpoint`` (``engine.py:2906,2601``).
"""

import functools
import os
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import flax.linen as nn

from deepspeed_tpu import comm as dist
from deepspeed_tpu.ops.adagrad.cpu_adagrad import adagrad
from deepspeed_tpu.ops.adam.fused_adam import fused_adam
from deepspeed_tpu.ops.lamb.fused_lamb import fused_lamb
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.fp16.loss_scaler import (LossScaleState, OverflowWatcher, create_loss_scaler,
                                                    has_overflow)
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
from deepspeed_tpu.runtime.resilience.faults import fault_point
from deepspeed_tpu.runtime.zero.planner import ZeroPlan, build_plan, resolve_topology_axes
from deepspeed_tpu.utils import trace
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (TRAIN_BATCH_TIMER, NoopTimer, SynchronizedWallClockTimer, ThroughputTimer)

MEMORY_OPT_ALLREDUCE_SIZE = 500000000


class TrainState(NamedTuple):
    """The engine's entire mutable state as one pytree (donated each step)."""
    step: jax.Array  # i32, optimizer steps taken (incl. overflow-skipped)
    params: Any  # fp32 master params (unboxed pytree)
    opt_state: Any
    loss_scale: LossScaleState


def default_causal_lm_loss(outputs, batch):
    """Default loss: next-token cross entropy over ``input_ids``/``labels``.
    MoE models return ``(logits, aux_loss)`` — the (already-scaled)
    load-balancing loss is added (reference adds ``l_aux`` in the client
    loss; here it rides along automatically)."""
    from deepspeed_tpu.models.gpt2 import cross_entropy_loss

    labels = batch.get("labels", batch["input_ids"]) if isinstance(batch, dict) else batch
    if isinstance(outputs, (tuple, list)):
        logits, aux_loss = outputs[0], outputs[1]
    else:
        logits, aux_loss = outputs, 0.0
    return cross_entropy_loss(logits[:, :-1], labels[:, 1:]) + aux_loss


def _cast_floating(tree, dtype):
    return jax.tree.map(lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p, tree)


def _truncate_seq(batch, seqlen: int):
    """Host-side truncation of every [batch, seq, ...] leaf to ``seqlen``
    tokens (curriculum learning, seqlen metric)."""
    def trunc(x):
        x = np.asarray(x)
        if x.ndim >= 2 and x.shape[1] > seqlen:
            return x[:, :seqlen]
        return x
    return jax.tree.map(trunc, batch)



def _comm_dtype(config):
    """Resolve ``communication_data_type`` (reference engine property
    ``engine.py:616``: the dtype gradients ride the wire in). None/fp32 ->
    no recast; "fp16"/"bf16" halve the dense-path reduction payload (the
    reference reduces in the comm dtype the same way; qcomm/1-bit own
    their wire formats)."""
    name = getattr(config, "communication_data_type", None)
    if name is None:
        return None
    # NB: "bf16" works on TPU; current XLA CPU check-fails compiling bf16
    # reduce-scatters inside large programs — use fp16 for CPU runs
    from deepspeed_tpu.runtime.config_utils import dtype_names
    resolved = dtype_names().get(str(name).lower())
    if resolved is None or not jnp.issubdtype(resolved, jnp.floating):
        raise ValueError(f"communication_data_type {name!r}: expected fp16/bf16/fp32 "
                         f"(or float16/bfloat16/float32/half/float)")
    return None if resolved == jnp.float32 else resolved


def _global_norm(tree):
    from deepspeed_tpu.runtime.utils import global_norm_l2
    return global_norm_l2(tree)


def _apply_program_knobs(module, config):
    """Rebuild ``module`` around a model config carrying the engine-level
    blocks that shape the traced program: "program" (remat policy / LM-head
    chunk / projection fusion), "moe" (dispatch route + permutation kernel)
    and "attention" (flash block geometry, merged over the model's own
    ``attention_blocks`` spec). The one way an engine block reaches a
    module: per engine, never process-wide. A set knob the model family
    doesn't declare raises (a silently dropped knob would price one
    program and run another)."""
    import dataclasses

    updates = {**config.program_config.model_updates(), **config.moe_config.model_updates()}
    blocks = config.attention_config.geometry_fields()
    if not updates and not blocks:
        return module
    mcfg = getattr(module, "config", None)
    if mcfg is None or not dataclasses.is_dataclass(mcfg):
        raise ValueError(
            f"'program'/'moe'/'attention' config block set but {type(module).__name__} "
            f"carries no dataclass model config to apply it to")
    if blocks:
        from deepspeed_tpu.ops.pallas.attention_geometry import from_dict, parse_spec
        updates["attention_blocks"] = from_dict(blocks).merged_over(
            parse_spec(getattr(mcfg, "attention_blocks", None))).spec()
    missing = sorted(f for f in updates if not hasattr(mcfg, f))
    if missing:
        raise ValueError(
            f"engine config blocks set {missing} but {type(mcfg).__name__} does not "
            f"declare those fields — the knob would silently not apply")
    changed = {f: v for f, v in updates.items() if getattr(mcfg, f) != v}
    if not changed:
        return module
    return module.clone(config=dataclasses.replace(mcfg, **changed))


class DeepSpeedEngine:

    def __init__(self,
                 model: nn.Module,
                 config: DeepSpeedConfig,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 loss_fn: Optional[Callable] = None,
                 lr_scheduler: Optional[Callable] = None,
                 topology: Optional[MeshTopology] = None,
                 model_parameters=None,
                 training_data=None,
                 collate_fn=None,
                 dont_change_device=False):
        self.module = model
        self.config = config
        self.client_optimizer = optimizer
        self.loss_fn = loss_fn or default_causal_lm_loss
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self._pending_overflow = []  # deferred (step, overflow, loss_scale)
        self.skipped_steps = 0
        self._initial_params = model_parameters
        self.state: Optional[TrainState] = None
        self.plan: Optional[ZeroPlan] = None
        self._grad_acc = None  # forward/backward-shim accumulation buffer
        self._shim_losses = []

        if not dist.is_initialized():
            dist.init_distributed(verbose=False)
        if config.comms_config.comms_logger_enabled:
            dist.configure(config=config.comms_config.comms_logger)

        # -- topology (reference _configure_distributed_model engine.py:1050)
        if topology is None:
            axes = resolve_topology_axes(config.mesh_config, config.zero_config, jax.device_count())
            topology = MeshTopology(**axes)
        else:
            # explicit topology overrides the config's mesh block: re-resolve
            # the batch triangle against the actual DP world
            config.resolve_batch_for_dp(topology.data_parallel_size)
        self.topology = topology
        self.mesh = topology.mesh
        from deepspeed_tpu.parallel.topology import set_topology
        set_topology(topology)  # sequence-parallel attention finds the mesh here

        # -- attention winners cache ("attention.cache_file"): the path the
        # geometry resolver reads the autotuner's winners from, still set
        # process-wide (ROADMAP S2 decides whether geometry becomes constants)
        from deepspeed_tpu.ops.pallas import attention_geometry as _ag
        _ag.set_cache_path(config.attention_config.cache_file or None)

        # -- traced-program shape ("program", "moe" and "attention" config
        # blocks): rebuild the module around a replaced model config, so
        # remat policy, LM-head chunking, projection fusion, MoE route and
        # attention block geometry are ENGINE dimensions — what graft-search
        # enumerates and prices statically (analysis/search.py). Per-engine
        # (module.clone), never process-wide: two engines in one process can
        # trace two different program variants.
        self.module = _apply_program_knobs(self.module, config)

        # -- precision (reference engine.py:1056-1069 half()/bfloat16())
        if config.bfloat16_enabled:
            self.compute_dtype = jnp.bfloat16
        elif config.fp16_enabled:
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32
        self._fp16_mode = config.fp16_enabled

        # -- loss scaler (reference fp16/loss_scaler.py CreateLossScaler)
        if config.fp16_enabled:
            self._ls_state0, self._ls_update = create_loss_scaler(
                static_loss_scale=config.loss_scale, **config.dynamic_loss_scale_args)
        else:
            self._ls_state0, self._ls_update = create_loss_scaler(static_loss_scale=1.0)

        # -- lr schedule + optimizer (reference _configure_optimizer engine.py:1175)
        self.lr_scheduler = lr_scheduler
        if self.lr_scheduler is None and config.scheduler_name is not None:
            self.lr_scheduler = get_lr_schedule(config.scheduler_name, config.scheduler_params)
        self.optimizer = self._configure_optimizer()

        # -- timers/monitor (reference EngineTimers engine.py:146)
        self.timers = SynchronizedWallClockTimer() if config.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=config.train_batch_size,
                                          steps_per_output=config.steps_per_print)
        from deepspeed_tpu.monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(config.monitor_config)

        # -- telemetry (runtime/telemetry, graft-trace): JSONL event log +
        # drift over the host-side step spans, which go to the process's
        # recorder (utils/trace.py) whether or not the block is enabled.
        # The monitor is ONE subscriber of the event bus — TB/W&B/CSV keep
        # working unchanged, and every published batch also lands durably
        # in the JSONL when enabled.
        # Instrumentation is host-only by construction: the traced step
        # program must stay eqn-identical with telemetry on (rule R015,
        # scenario train_batch_telemetry) and within 2% step time (tier-1).
        from deepspeed_tpu.runtime.telemetry import RuntimeTelemetry
        self.telemetry = RuntimeTelemetry(config.telemetry_config,
                                          flush_every=config.steps_per_print,
                                          rank=dist.get_rank(),
                                          run_info_fn=self._telemetry_run_info,
                                          label="engine")
        if self.monitor.enabled:
            self.telemetry.subscribe(self.monitor.write_events)
        # -- resilience (runtime/resilience): host mirror of the compiled
        #    overflow-skip state + preemption-to-checkpoint signal handling
        _rcfg = config.resilience_config
        self._overflow_watcher = OverflowWatcher(abort_after=_rcfg.max_consecutive_overflows)
        self._resilience_events = []  # buffered monitor events from drains/fallbacks
        self._preemption = None
        self._preempt_save_dir = None
        self._preempt_exit = bool(_rcfg.exit_after_preempt_save)
        self._preempt_exit_code = int(_rcfg.preempt_exit_code)
        if _rcfg.preempt_save_dir:
            self.enable_preemption_checkpoint(_rcfg.preempt_save_dir)

        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        self._base_rng = jax.random.PRNGKey(config.seed)
        self._train_step_fn = None
        self._eval_step_fn = None
        self._micro_grad_fn = None
        self._apply_grads_fn = None
        self._moe_stats_fn = None  # jitted MoE gate-observability forward
        # defaults live here (not in _build_step_fns) because subclasses
        # override _build_step_fns but the base train_batch reads these
        self._onebit_cfg = None
        self._onebit_step_fn = None
        self._onebit_errors = None
        self._use_qcomm = False
        self._offload_enabled = False
        # derived from config here (not just _prepare_plan) because
        # train_batches routes on it before initialize_state has run —
        # and misconfigurations should fail at initialize(), not at the
        # first train_batch's lazy plan build
        _poff = config.zero_config.offload_param
        self._param_offload_enabled = (_poff is not None
                                       and getattr(_poff, "device", "none") not in (None, "none"))
        if self._param_offload_enabled:
            if config.zero_config.stage != 3:
                raise ValueError("offload_param requires ZeRO stage 3 "
                                 f"(got stage {config.zero_config.stage})")
            if config.zero_config.zero_quantized_weights:
                raise ValueError("offload_param does not compose with "
                                 "zero_quantized_weights (the QDQ transform would run "
                                 "on host-resident leaves); pick one")
        self._param_swapper = None
        self._zeroone_runner = None
        self._autotune = None  # (mode, raw config dict), set by entry.initialize
        # compression-in-forward (set via compression.init_compression)
        self._compression_pending = False
        self._compression_config = None
        # staged knowledge distillation (compression.init_compression with
        # teacher_model): in-graph teacher forward + scheduled loss mixing
        self._kd_config = None
        self._pending_student_init = None
        if config.quantize_training_config.get("enabled", False):
            # MoQ via config alone (no init_compression call) still resolves
            # once the param tree exists
            self._compression_pending = True
        self._compression_transform = None

        # -- curriculum learning (reference legacy surface,
        #    _configure_curriculum_scheduler_legacy engine.py:1283): for the
        #    seqlen metric the engine truncates batches itself — on TPU the
        #    difficulty IS the static sequence length, so the schedule's
        #    difficulty_step doubles as the recompile bucket
        cl_cfg = (config.raw_dict or {}).get("curriculum_learning", {})
        self.curriculum_scheduler = None
        self.curriculum_metric = None
        if cl_cfg.get("enabled", False):
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(cl_cfg)
            self.curriculum_metric = cl_cfg.get("curriculum_type", "seqlen")
            log_dist(f"curriculum learning enabled: metric={self.curriculum_metric} "
                     f"schedule={cl_cfg.get('schedule_type')}")

        # progressive layer drop (reference _configure_progressive_layer_drop;
        # engine.progressive_layer_drop is the host mirror users read, the
        # in-graph theta is computed from state.step in the train step so the
        # fused multi-step dispatch anneals it without recompiling)
        self.progressive_layer_drop = None
        if config.pld_enabled:
            import inspect
            from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(**config.pld_params)
            accepts = "pld_theta" in inspect.signature(type(self.module).__call__).parameters
            flag_on = bool(getattr(getattr(self.module, "config", None),
                                   "progressive_layer_drop", False))
            if not (accepts and flag_on):
                logger.warning("progressive_layer_drop enabled but the model will not "
                               "drop layers (model accepts pld_theta: %s, model config "
                               "progressive_layer_drop flag: %s) — set "
                               "progressive_layer_drop=True on a supporting model "
                               "config, e.g. GPT2Config; theta will anneal but no "
                               "layers will drop", accepts, flag_on)
            if config.zero_config.offload_optimizer is not None:
                logger.warning("progressive_layer_drop only applies on the fused "
                               "train_batch path; the offload-optimizer step runs "
                               "without layer dropping (theta still anneals)")

        log_dist(f"DeepSpeedEngine: zero_stage={config.zero_optimization_stage} "
                 f"dtype={self.compute_dtype.__name__} mesh={dict(self.mesh.shape)}")

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def _compressed_comm_eligible(self, optimizer_name: str) -> bool:
        """Real compressed collectives (1-bit Adam, 0/1 Adam) need replicated
        params/opt state (stage 0) on a pure-DP multi-device mesh without
        MoE/offload.

        A model-parallel mesh RAISES instead of degrading: the reference's cupy backends have the same pure-DP scope, and
        a user asking for 1-bit wire compression on a TP/pipe mesh would
        otherwise silently train with dense collectives — paying full wire
        bytes while believing they bought the 32x compression."""
        if (self.config.optimizer_name != optimizer_name
                or self.client_optimizer is not None):
            return False

        def conflict(what, fix):
            raise ValueError(
                f"{optimizer_name}'s compressed collective cannot run with {what} "
                f"(reference 1-bit/0-1 cupy backend scope: replicated state on a "
                f"pure-DP mesh); {fix}")

        # single-device runs stay quiet on EVERY branch: there is no
        # collective to compress, so nothing the config promised is lost
        # (dev/test runs of a prod config must not crash)
        if self.mesh.size == 1:
            return False
        pure_dp = all(self.mesh.shape[a] == 1 for a in ("pipe", "tensor", "sequence", "expert"))
        if not pure_dp:
            mp_axes = {a: int(self.mesh.shape[a]) for a in
                       ("pipe", "tensor", "sequence", "expert") if self.mesh.shape[a] > 1}
            conflict(f"model-parallel mesh axes {mp_axes}",
                     "use a plain optimizer on this mesh or drop the axes")
        off = self.config.zero_config.offload_optimizer
        if off is not None and getattr(off, "device", "none") not in (None, "none"):
            conflict("offload_optimizer", "pick one of the two")
        mcfg = getattr(self.module, "config", None)
        if mcfg is not None and getattr(mcfg, "moe_num_experts", 0) > 0:
            conflict("an MoE model", "use a plain optimizer for MoE")
        if self.config.zero_optimization_stage != 0:
            conflict(f"ZeRO stage {self.config.zero_optimization_stage}",
                     "compressed collectives need replicated state (stage 0)")
        return True

    def _configure_optimizer(self) -> optax.GradientTransformation:
        """Reference ``_configure_basic_optimizer`` (``engine.py:1225``):
        config name → built-in optimizer; a client-supplied optax transform
        wins (reference: client optimizer object passed to initialize)."""
        if self.client_optimizer is not None:
            return self.client_optimizer
        name = self.config.optimizer_name or C.ADAM_OPTIMIZER
        params = dict(self.config.optimizer_params or {})
        lr = params.pop("lr", 1e-3)
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler
        if name in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER):
            adam_w_mode = params.pop("adam_w_mode", name == C.ADAMW_OPTIMIZER)
            # torch_adam/fused flags are meaningless on TPU; accept & drop
            params.pop("torch_adam", None)
            params.pop("fused", None)
            if self.config.optimizer_legacy_fusion:
                # the UNFUSED Adam variant (``optimizer.legacy_fusion``):
                # optax's chained composition — separate scale_by_adam /
                # decay / lr stages with their own intermediate update
                # trees, more eqns and transients than the single
                # tree-map chain XLA fuses in fused_adam. Same math; a
                # real optimizer-fusion dimension for graft-search, and
                # the escape hatch when a client transform must compose
                # with the moment updates.
                b1, b2 = params.pop("betas", (0.9, 0.999))
                eps = params.pop("eps", 1e-8)
                wd = params.pop("weight_decay", 0.0)
                params.pop("bias_correction", None)  # optax always corrects
                if params:
                    raise ValueError(f"legacy_fusion adam does not accept {sorted(params)}")
                if adam_w_mode:
                    return optax.adamw(learning_rate=lr, b1=b1, b2=b2, eps=eps,
                                       weight_decay=wd)
                pre = [optax.add_decayed_weights(wd)] if wd else []
                return optax.chain(*pre, optax.scale_by_adam(b1=b1, b2=b2, eps=eps),
                                   optax.scale_by_learning_rate(lr))
            return fused_adam(lr=lr, adam_w_mode=adam_w_mode, **params)
        if name in (C.ONEBIT_ADAM_OPTIMIZER, C.ZERO_ONE_ADAM_OPTIMIZER, C.ONEBIT_LAMB_OPTIMIZER):
            from deepspeed_tpu.runtime.fp16.onebit import get_onebit_optimizer
            if name in (C.ONEBIT_ADAM_OPTIMIZER, C.ZERO_ONE_ADAM_OPTIMIZER,
                        C.ONEBIT_LAMB_OPTIMIZER) and self._compressed_comm_eligible(name):
                # the engine's compressed-collective step owns compression;
                # the transform skips its internal QDQ and the dead
                # full-size error-feedback tree
                params["external_comm"] = True
            return get_onebit_optimizer(name, lr=lr, **params)
        if name == C.LAMB_OPTIMIZER:
            return fused_lamb(lr=lr, **params)
        if name == C.ADAGRAD_OPTIMIZER:
            return adagrad(lr=lr, **params)
        if name == C.SGD_OPTIMIZER:
            mom = params.pop("momentum", 0.0)
            return optax.sgd(learning_rate=lr, momentum=mom or None)
        if name == C.LION_OPTIMIZER:
            return optax.lion(learning_rate=lr, **params)
        raise ValueError(f"unknown optimizer {name!r}")

    # ------------------------------------------------------------------
    # state init (≅ zero.Init sharded construction, partition_parameters.py)
    # ------------------------------------------------------------------
    def _maybe_autotune(self, example_batch):
        """``--autotuning tune|run`` (reference ``launcher/runner.py:358``):
        engages on the first batch, when shapes are known. ``tune`` writes
        results and exits; ``run`` adopts the optimal config and trains on."""
        if not self._autotune:
            return
        mode, raw_cfg = self._autotune
        self._autotune = None
        from deepspeed_tpu.autotuning import Autotuner
        tuner = Autotuner(model=self.module, config=raw_cfg,
                          example_batch=example_batch, topology=self.topology)
        best = tuner.tune()
        tuner.print_tuning_results()
        if mode == "tune":
            # experiments only — results are on disk for the real launch
            # (reference exits after tuning in this mode); exit even with no
            # winner, or the user pays for an unrequested training run
            raise SystemExit(0 if best is not None else 1)
        if best is None:
            log_dist("autotuning: no runnable candidate; keeping the user config")
            return
        log_dist(f"autotuning: adopting {best.name} "
                 f"(train_batch_size={best.config['train_batch_size']})")
        self.config = DeepSpeedConfig(best.config, dp_world_size=self.topology.data_parallel_size)
        self.optimizer = self._configure_optimizer()
        # everything that captured the old batch triangle must follow it
        self.tput_timer.batch_size = self.config.train_batch_size
        if self.training_dataloader is not None:
            self.training_dataloader = self.deepspeed_io(
                self.training_dataloader.dataset,
                collate_fn=getattr(self.training_dataloader, "collate_fn", None))
            self._train_iter = None  # drop any iterator over the old loader

    def _prepare_plan(self, example_batch, rng):
        """Shared planning core for ``initialize_state`` (concrete) and
        ``abstract_state`` (costing): ZeRO plan, shardings, offload
        detection — identical semantics in both paths by construction.
        Returns ``(init_params_fn, abstract_params, abstract_opt_state)``."""
        # re-pin the process-global topology: another engine constructed since
        # may have repointed it, and model layers (ring attention, MoE
        # dispatch) resolve the mesh through get_topology() at trace time
        from deepspeed_tpu.parallel.topology import set_topology
        set_topology(self.topology)
        example_ids = self._example_ids(example_batch)
        # extra module inputs (decoder_input_ids, attention_mask, ...) at
        # batch size 1, matching example_ids — encoder-decoder models need
        # them present at parameter init
        def example_extra(v):
            v = np.asarray(v)
            if v.ndim >= 3:  # [gas, micro, ...] batches: drop the gas dim
                v = v[0]
            return jnp.asarray(v[:1])

        extras = {k: example_extra(v)
                  for k, v in self._module_kwargs(example_batch).items()
                  if np.ndim(v) > 0}

        def init_params(key):
            variables = self.module.init(key, example_ids, deterministic=True, **extras)
            return nn.meta.unbox(variables["params"])

        # the plan needs the BOXED abstract params — flax logical-axis
        # metadata (nn.Partitioned) is what maps params onto mesh axes
        aboxed = jax.eval_shape(
            lambda k: self.module.init(k, example_ids, deterministic=True, **extras), rng)
        self.plan = build_plan(aboxed["params"], self.config.zero_config, self.topology)
        param_shardings = self.plan.param_shardings()
        aparams = jax.eval_shape(init_params, rng)

        poff = self.config.zero_config.offload_param
        self._param_offload_enabled = (poff is not None
                                       and getattr(poff, "device", "none") not in (None, "none"))
        if self._param_offload_enabled:
            # reference config contract: offload_param is a ZeRO-3 feature
            # (zero/config.py validator "offload_param ... stage 3 only")
            if self.config.zero_config.stage != 3:
                raise ValueError("offload_param requires ZeRO stage 3 "
                                 f"(got stage {self.config.zero_config.stage})")
            if self.config.zero_config.zero_quantized_weights:
                raise ValueError("offload_param does not compose with "
                                 "zero_quantized_weights (the QDQ transform would run "
                                 "on host-resident leaves); pick one")
            # resting placement: pinned host memory, same fsdp sharding —
            # every step streams the shards through the chip (param_offload.py)
            from deepspeed_tpu.runtime.zero.param_offload import host_shardings
            param_shardings = host_shardings(param_shardings)

        off = self.config.zero_config.offload_optimizer
        self._offload_enabled = off is not None and getattr(off, "device", "none") not in (None, "none")
        if self._offload_enabled:
            # moments live off-device (host RAM / NVMe): no optax state.
            # fp16 composes: the grads-only device program scales the loss
            # and unscales the gradients BEFORE they leave the chip
            # (reference stage_1_and_2.py:1086 — unscale-and-clip on
            # device, fp32 master update on host), so the host Adam only
            # ever sees unscaled fp32 gradients and overflow steps skip
            # the host update entirely (_offload_train_batch).
            aopt, opt_shardings = {}, {}
        else:
            aopt = jax.eval_shape(self.optimizer.init, aparams)
            opt_shardings = self.plan.optstate_shardings(aopt)

        repl = NamedSharding(self.mesh, P())
        self.state_shardings = TrainState(step=repl,
                                          params=param_shardings,
                                          opt_state=opt_shardings,
                                          loss_scale=jax.tree.map(lambda _: repl, self._ls_state0))
        return init_params, aparams, aopt

    def initialize_state(self, example_batch, rng: Optional[jax.Array] = None):
        """Build the sharded TrainState directly into its final placement:
        params are *initialized shard-by-shard on their owning devices*
        (jit with out_shardings), never materialized replicated — the TPU
        answer to ``zero.Init`` construction-time partitioning."""
        self._maybe_autotune(example_batch)
        if self.state is not None:
            from deepspeed_tpu.parallel.topology import set_topology
            set_topology(self.topology)
            return
        rng = rng if rng is not None else self._base_rng
        tel = self.telemetry
        with tel.recorder.span("initialize_state", source=tel.source, marks=trace.TOTAL):
            with tel.span("plan"):
                init_params, _, _ = self._prepare_plan(example_batch, rng)
            with tel.span("state_init"):
                self._init_state(init_params, rng)
            with tel.span("build_step"):
                self._maybe_apply_student_init()
                self._setup_offload_optimizer()
                self._setup_param_offload()
                self._build_step_fns()

    def _init_state(self, init_params, rng) -> None:
        """Params and optimizer state made where they rest, and the
        ``TrainState`` around them."""
        param_shardings = self.state_shardings.params
        opt_shardings = self.state_shardings.opt_state

        if self._initial_params is not None:
            # migrate places host memory kinds (offload_param: param_shardings
            # rest in pinned_host) — shard-wise on multi-process meshes, where
            # a plain device_put reshards through a jitted identity the
            # XLA:CPU partitioner rejects (param_offload.migrate)
            from deepspeed_tpu.runtime.zero.param_offload import migrate
            params = migrate(nn.meta.unbox(self._initial_params), param_shardings)
        elif self._param_offload_enabled:
            # jit out_shardings cannot carry host memory kinds through the
            # SPMD partitioner (see param_offload.py): init shard-by-shard
            # onto device, then migrate to the pinned-host resting placement
            # (transient device footprint = the offload-free sharded params;
            # beyond-HBM models load via _initial_params / checkpoint restore,
            # which go straight to host)
            params = jax.jit(init_params, out_shardings=self.plan.param_shardings())(rng)
        else:
            params = jax.jit(init_params, out_shardings=param_shardings)(rng)

        if self._offload_enabled:
            opt_state = {}
        elif self._param_offload_enabled and self._initial_params is not None:
            # beyond-HBM path: never materialize the loaded params on
            # device. Optimizer state depends only on shapes/dtypes (optax
            # moments init as zeros), so build it from in-graph zeros — XLA
            # folds the zero params away and emits the sharded zero moments
            # directly
            shapes = jax.tree.map(lambda l: jax.ShapeDtypeStruct(jnp.shape(l), l.dtype), params)
            opt_state = jax.jit(
                lambda: self.optimizer.init(
                    jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)),
                out_shardings=opt_shardings)()
        else:
            # params may transiently be the device copy (offload_param init
            # path above) — optimizer.init consumes it before migration
            opt_state = jax.jit(self.optimizer.init, out_shardings=opt_shardings)(params)

        if self._param_offload_enabled and self._initial_params is None:
            from deepspeed_tpu.runtime.zero.param_offload import migrate
            params_dev, params = params, migrate(params, param_shardings)
            jax.block_until_ready(params)
            del params_dev

        repl = NamedSharding(self.mesh, P())
        ls_state = jax.device_put(self._ls_state0, repl)  # graft-lint: waive R008 jax-owned jnp scalars (loss_scaler.py)
        self.state = TrainState(step=jax.device_put(jnp.zeros([], jnp.int32), repl),  # graft-lint: waive R008 jax-owned zeros
                                params=params,
                                opt_state=opt_state,
                                loss_scale=ls_state)

    def abstract_state(self, example_batch, rng: Optional[jax.Array] = None) -> TrainState:
        """The TrainState as a ``ShapeDtypeStruct`` pytree — plan, shardings
        and step functions are built but NO device memory is allocated. The
        autotuner's entry point: candidates are compiled and costed from
        this without paying per-candidate HBM."""
        rng = rng if rng is not None else self._base_rng
        _, aparams, aopt = self._prepare_plan(example_batch, rng)
        als = jax.tree.map(lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
                           self._ls_state0)
        abstract = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                              params=aparams, opt_state=aopt, loss_scale=als)
        self._build_step_fns()
        return abstract

    def _step_program_args(self, example_batch):
        """The device step program this engine would dispatch, as an AOT
        pair ``(jitted_fn, abstract_args)`` — shared by
        :meth:`lower_train_step` (autotuner costing) and
        :meth:`traced_programs` (graft-lint analysis)."""
        abstract = self.abstract_state(example_batch)
        gas = self.config.gradient_accumulation_steps

        def leaf(x):
            x = np.asarray(x)
            assert x.shape[0] % gas == 0, f"global batch {x.shape[0]} not divisible by GAS {gas}"
            return jax.ShapeDtypeStruct((gas, x.shape[0] // gas) + x.shape[1:], x.dtype)

        abatch = jax.tree.map(leaf, example_batch)
        arng = jax.ShapeDtypeStruct(self._base_rng.shape, self._base_rng.dtype)
        if self._offload_enabled:
            # offload_optimizer: the device program is the grads-only pass
            # (the update runs on host) — its memory_analysis IS the
            # candidate's HBM footprint, which is what the autotuner prunes on
            ascale = jax.ShapeDtypeStruct((), jnp.float32)
            return self._grads_only_fn, (abstract.params, abatch, arng, ascale)
        if getattr(self, "_param_offload_enabled", False):
            # the offload step fn splits (params, rest) so the device-resident
            # rest can be donated; memory_analysis() of this lowering is the
            # HBM-residency evidence (host params land in host_argument_size)
            rest = (abstract.step, abstract.opt_state, abstract.loss_scale)
            return self._train_step_fn, (abstract.params, rest, abatch, arng)
        return self._train_step_fn, (abstract, abatch, arng)

    def lower_train_step(self, example_batch):
        """AOT-lower the fused train step against abstract state/batch; the
        result's ``.compile()`` exposes XLA ``memory_analysis()`` and
        ``cost_analysis()`` — the TPU replacement for the reference
        autotuner's experiment launches (``autotuning/autotuner.py:1052``)."""
        fn, args = self._step_program_args(example_batch)
        return fn.lower(*args)

    def traced_programs(self, example_batch, lower: bool = True):
        """Expose the engine's jitted step for static analysis
        (``deepspeed_tpu/analysis``, ``tools/graft_lint.py``): trace-only —
        no compilation, no device buffers. Returns ``{name: {"jaxpr":
        ClosedJaxpr, "hlo_text": StableHLO str, "metadata": {...}}}``;
        metadata pre-declares what the rules should expect of THIS engine
        (donation on the non-offload step, the MoE [S,E,C] signature when
        the model routes through experts, mesh multiplicity for the
        sharding-coverage rule). ``lower=False`` skips the StableHLO
        lowering entirely (``hlo_text``/``lower`` come back None) — at
        real model sizes lowering dominates the trace by an order of
        magnitude, and graft-search prices dozens of candidates from the
        jaxpr alone (analysis/search.py)."""
        fn, args = self._step_program_args(example_batch)
        traced = fn.trace(*args)
        if lower:
            # lower from the existing trace — fn.lower(*args) would re-trace
            # the whole step (seconds per call at real model sizes)
            lowered = traced.lower()
            hlo_text = lowered.as_text()
        else:
            lowered, hlo_text = None, None
        metadata = {
            # the offload paths intentionally do NOT donate params (host
            # masters / cross-memory-kind aliasing is illegal)
            "expect_donation": not self._offload_enabled,
            "multi_device": self.mesh.devices.size > 1,
            # the cost pass (analysis/cost.py) attributes wire bytes per
            # mesh axis and sizes replica groups from this
            "mesh_axes": {str(a): int(s) for a, s in self.mesh.shape.items()},
        }
        metadata.update(self.config.zero_config.cost_metadata(
            fsdp_size=int(self.mesh.shape.get("fsdp", 1))))
        cfg_model = getattr(self.module, "config", None)
        # the program knobs THIS trace carried — graft-search's candidate
        # evidence, and the audit trail that a banked rung ran the variant
        # it claims
        from deepspeed_tpu.runtime.config import PROGRAM_MODEL_FIELDS
        knobs = {field: getattr(cfg_model, mf)
                 for field, mf in PROGRAM_MODEL_FIELDS.items()
                 if cfg_model is not None and hasattr(cfg_model, mf)}
        if knobs:
            knobs["optimizer_fusion"] = (
                "client" if self.client_optimizer is not None else
                "chained" if self.config.optimizer_legacy_fusion else "fused")
            metadata["program_knobs"] = knobs
        moe_experts = getattr(cfg_model, "moe_num_experts", 0) if cfg_model is not None else 0
        if moe_experts:
            from deepspeed_tpu.moe.sharded_moe import _num_groups, sec_signature
            batch_leaf = np.asarray(jax.tree.leaves(example_batch)[0])
            micro = batch_leaf.shape[0] // self.config.gradient_accumulation_steps
            seq = batch_leaf.shape[1] if batch_leaf.ndim > 1 else 1
            tokens = (micro * seq) // _num_groups(micro)
            metadata["moe_sec"] = [sec_signature(
                tokens, moe_experts,
                getattr(cfg_model, "moe_capacity_factor", 1.0),
                getattr(cfg_model, "moe_min_capacity", 8),
                k=getattr(cfg_model, "moe_k", 1))]
            if getattr(cfg_model, "moe_route", "sorted") == "sorted":
                sig = metadata.setdefault("collective_signature", [])
                sig.append({"layer": "jaxpr", "kind": "dense_dispatch", "count": 0,
                            "note": "sorted MoE route: the a2a endpoints are fed "
                                    "by permutation, never an [S,E,C] einsum"})
        return {"train_step": {"jaxpr": traced.jaxpr, "hlo_text": hlo_text,
                               "metadata": metadata,
                               "lower": (lambda: lowered) if lowered is not None else None}}

    # ------------------------------------------------------------------
    # telemetry (runtime/telemetry): run-header provenance + static price
    # ------------------------------------------------------------------
    def _telemetry_run_info(self):
        """What the JSONL run header stamps: enough provenance to tie every
        drift ratio back to the exact program shape that produced it."""
        import jaxlib

        from deepspeed_tpu.runtime.telemetry import config_signature
        info = {
            "config_sig": config_signature(self.config.raw_dict or {}),
            "pid": os.getpid(),
            "jax_version": jax.__version__,
            "jaxlib_version": getattr(jaxlib, "__version__", "unknown"),
            "backend": jax.default_backend(),
            "mesh_axes": {str(a): int(s) for a, s in self.mesh.shape.items()},
            "world_size": dist.get_world_size(),
            "model": type(self.module).__name__,
            "dtype": self.compute_dtype.__name__,
            "zero_stage": self.config.zero_optimization_stage,
            "train_batch_size": self.config.train_batch_size,
            "gradient_accumulation_steps": self.config.gradient_accumulation_steps,
        }
        info.update(self._telemetry_run_extra())
        return info

    def _telemetry_run_extra(self):
        """Subclass hook (PipelineEngine adds its schedule block)."""
        return {}

    def _maybe_write_telemetry_header(self, batch):
        """First-step lazy run header: the static price needs a traced
        program, which needs a concrete batch shape. Jaxpr-only trace
        (``lower=False`` — the graft-search fast path); priced once per
        run, before the warm steps a bench would time. Pricing failure
        degrades to an error field — observability never kills a step."""
        if not self.telemetry.wants_run_header:
            return
        price = None
        if getattr(self.config.telemetry_config, "static_price", True):
            try:
                from deepspeed_tpu.analysis import static_price_from_programs
                price = static_price_from_programs(self.traced_programs(batch, lower=False))
            except Exception as e:  # noqa: BLE001
                price = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
        self.telemetry.write_run_header(static_price=price)  # run_info via run_info_fn

    # ------------------------------------------------------------------
    # ZeRO-Offload / ZeRO-Infinity: optimizer states off-device
    # (reference stage_1_and_2 cpu_offload / stage3 + swap_tensor; SURVEY §7.3)
    # ------------------------------------------------------------------
    def _accumulate_grads(self, params, batch, rng, scale, grad_shardings, gas, clip, fp16,
                          params_transform=None, model_extra=None, step_counts=None):
        """The shared fwd+bwd core: GAS microbatch scan, 1/gas averaging,
        quantized or full-precision ZeRO reduction, clipping, overflow.
        Used by the fused on-device step AND the offload grads-only step so
        the two paths cannot drift. ``step_counts`` (a dict, the fused step's
        where the module names step counts): the forward's device-side counts
        come back in it under ``"counts"``, [gas, layers, counts] int32.
        ``params_transform`` (compression-in-
        forward) runs INSIDE the grad closure so masks gate gradients and
        the quantization STE applies. ``model_extra`` (traced scalars such
        as the PLD theta) merges into every microbatch dict so
        ``_module_kwargs`` forwards it to the model."""
        keys = jax.random.split(rng, gas)
        loss_for = self._loss_for
        if model_extra:
            base_loss_for_extra = loss_for

            def loss_for(p, mb, key, scale, train=True, **kw):
                # raw-array batches are normalized to a dict so the extras
                # (pld_theta) still reach the model
                mb = dict(mb, **model_extra) if isinstance(mb, dict) \
                    else dict({"input_ids": mb}, **model_extra)
                return base_loss_for_extra(p, mb, key, scale, train=train, **kw)
        loss_for_with_extra = loss_for
        if params_transform is not None:
            base_loss_for = loss_for

            def loss_for(p, mb, key, scale, train=True, **kw):
                return base_loss_for(params_transform(p), mb, key, scale, train=train, **kw)

        if getattr(self, "_use_qcomm", False):
            # ZeRO++ real quantized collectives: the whole gather→scan→reduce
            # runs as one shard_map over (data, fsdp) with int8/int4 payloads
            # on the wire (qcomm.py; reference coalesced_collectives.py:31,
            # partition_parameters.py:628)
            from deepspeed_tpu.runtime.zero.qcomm import qcomm_accumulate
            zc = self.config.zero_config
            # the model_extra wrapper (PLD theta) rides into the qcomm trace;
            # params_transform stays fused-path-only (warning at setup)
            fn = qcomm_accumulate(
                loss_for_with_extra, self.mesh, self.plan.param_specs, self.plan.grad_specs,
                batch, self._batch_spec(with_gas_dim=True), gas=gas,
                quantized_weights=bool(zc.zero_quantized_weights),
                quantized_gradients=bool(zc.zero_quantized_gradients),
                wire_dtype=self.compute_dtype,
                grad_wire_dtype=_comm_dtype(self.config))
            self._qcomm_tracing = True
            try:
                loss_mean, grads = fn(params, batch, keys, scale)
            finally:
                self._qcomm_tracing = False
            gnorm = _global_norm(grads)
            overflow = has_overflow(grads) if fp16 else ~jnp.isfinite(gnorm)
            if clip > 0:
                factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)
            return loss_mean, grads, gnorm, overflow

        if step_counts is not None:
            loss_for = functools.partial(loss_for, with_counts=True)

        def micro(acc, xs):
            mb, key = xs
            # the aux is the loss, or (loss, counts) where the step counts
            (_, aux), grads = jax.value_and_grad(loss_for, has_aux=True)(params, mb, key, scale)
            grads = _cast_floating(grads, jnp.float32)
            return jax.tree.map(jnp.add, acc, grads), aux

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, losses = jax.lax.scan(micro, zeros, (batch, keys))
        if step_counts is not None:
            losses, step_counts["counts"] = losses      # [gas, layers, counts]
        # average over microbatches and unscale (reference engine.py:1868
        # scales loss by 1/GAS; fp16 unscaling in optimizer step)
        grads = jax.tree.map(lambda g: g / (gas * scale), grads)
        if self.config.zero_config.zero_quantized_gradients:
            grads = self._quantize_reduced_grads(grads, jax.random.fold_in(rng, 1))
        # ZeRO stage>=2: keep only the local shard after reduction
        grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        gnorm = _global_norm(grads)
        overflow = has_overflow(grads) if fp16 else ~jnp.isfinite(gnorm)
        if clip > 0:
            factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads = jax.tree.map(lambda g: g * factor, grads)
        return losses.mean(), grads, gnorm, overflow

    def _build_onebit_step_fn(self, batch):
        """Compression-phase 1-bit Adam step: one shard_map over the DP axes
        where each device computes LOCAL gradients, updates the shared
        momentum with them, and the only cross-device traffic is the
        two-phase 1-bit compressed momentum allreduce
        (``runtime/comm/compressed.py``; reference ``nccl.py:51`` +
        ``fp16/onebit/adam.py:307``). Variance is frozen (post-freeze_step
        semantics); error-feedback buffers are per-device."""
        import jax.flatten_util

        from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce

        ob = self._onebit_cfg
        b1, _ = ob["betas"]
        eps, wd, lr = ob["eps"], ob["weight_decay"], ob["lr"]
        lamb_mode = ob.get("mode") == "lamb"
        gas = self.config.gradient_accumulation_steps
        fp16 = self._fp16_mode
        mesh = self.mesh
        dp_axes = ("data", "fsdp")
        world = mesh.shape["data"] * mesh.shape["fsdp"]
        from deepspeed_tpu.runtime.comm.compressed import padded_chunk_size
        n_flat = sum(int(np.prod(s)) for s in jax.tree.leaves(
            self.plan.param_shapes, is_leaf=lambda x: isinstance(x, tuple)))
        m_chunk = padded_chunk_size(n_flat, world)

        err_sharding = NamedSharding(mesh, P(dp_axes))
        if self._onebit_errors is None:
            zeros = jax.jit(lambda: (jnp.zeros((world, n_flat), jnp.float32),
                                     jnp.zeros((world, m_chunk), jnp.float32)),
                            out_shardings=(err_sharding, err_sharding))
            self._onebit_errors = zeros()

        batch_spec = self._batch_spec(with_gas_dim=True)
        batch_in_specs = jax.tree.map(lambda x: P(*batch_spec[:x.ndim]), batch)

        def body(params, opt_state, ew, es, local_batch, keys, scale):
            dp_idx = jax.lax.axis_index(dp_axes)

            def micro(acc, xs):
                mb, key = xs
                key = jax.random.fold_in(key, dp_idx)
                # manual shard_map body: activation sharding constraints off
                from deepspeed_tpu.models.common import activation_constraints_disabled
                with activation_constraints_disabled():
                    (_, loss), grads = jax.value_and_grad(self._loss_for, has_aux=True)(
                        params, mb, key, scale)
                grads = _cast_floating(grads, jnp.float32)
                return jax.tree.map(jnp.add, acc, grads), loss

            zeros_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(micro, zeros_g, (local_batch, keys))
            flat_g, unravel = jax.flatten_util.ravel_pytree(
                jax.tree.map(lambda g: g / (gas * scale), grads))
            local_bad = ~jnp.isfinite(jnp.sum(jnp.abs(flat_g)))
            overflow = jax.lax.pmax(local_bad.astype(jnp.int32), dp_axes).astype(bool)

            # count reverts on overflow-skipped steps (the baseline path
            # reverts the whole opt_state; schedules must not drift)
            count = jnp.where(overflow, opt_state.count, opt_state.count + 1)
            step_lr = lr(count) if callable(lr) else lr
            flat_m, _ = jax.flatten_util.ravel_pytree(opt_state.exp_avg)
            flat_v, _ = jax.flatten_util.ravel_pytree(opt_state.exp_avg_sq)
            flat_p, _ = jax.flatten_util.ravel_pytree(params)

            m_local = b1 * flat_m + (1 - b1) * flat_g
            m_avg, ew_new, es_new = compressed_allreduce(m_local, ew[0], es[0], dp_axes, world)
            if lamb_mode:
                # 1-bit LAMB (reference onebit/lamb.py:443): Adam-style
                # direction from the compressed momentum, scaled per tensor
                # by the trust ratio FROZEN at freeze_step
                m_tree = unravel(m_avg)

                def leaf_update(p, m, v, frozen):
                    d = m / (jnp.sqrt(v) + eps)
                    if wd > 0.0:
                        d = d + wd * p
                    return p - step_lr * frozen * d

                p_tree_new = jax.tree.map(leaf_update, params, m_tree,
                                          opt_state.exp_avg_sq, opt_state.frozen_ratio)
                flat_p_new, _ = jax.flatten_util.ravel_pytree(p_tree_new)
            else:
                upd = m_avg / (jnp.sqrt(flat_v) + eps)
                if wd > 0.0:
                    upd = upd + wd * flat_p
                flat_p_new = flat_p - step_lr * upd

            keep = lambda new, old: jnp.where(overflow, old, new)
            flat_p_new = keep(flat_p_new, flat_p)
            m_avg = keep(m_avg, flat_m)
            ew_new = keep(ew_new, ew[0])
            es_new = keep(es_new, es[0])

            new_params = unravel(flat_p_new)
            new_opt = opt_state._replace(count=count, exp_avg=unravel(m_avg))
            loss = jax.lax.pmean(losses.mean(), dp_axes)
            gnorm = jnp.sqrt(jnp.sum(jnp.square(m_avg)))  # compressed-momentum norm
            return new_params, new_opt, ew_new[None], es_new[None], loss, gnorm, overflow

        p_specs = jax.tree.map(lambda _: P(), self.state.params)
        opt_specs = jax.tree.map(lambda _: P(), self.state.opt_state)
        in_specs = (p_specs, opt_specs, P(dp_axes), P(dp_axes), batch_in_specs, P(), P())
        out_specs = (p_specs, opt_specs, P(dp_axes), P(dp_axes), P(), P(), P())
        smapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                                check_vma=False)

        def step(state, errors, device_batch, rng):
            scale = state.loss_scale.loss_scale if fp16 else jnp.float32(1.0)
            keys = jax.random.split(rng, gas)
            new_params, new_opt, ew, es, loss, gnorm, overflow = smapped(
                state.params, state.opt_state, errors[0], errors[1], device_batch, keys, scale)
            new_ls = self._ls_update(state.loss_scale, overflow)
            new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt,
                                   loss_scale=new_ls)
            metrics = {"loss": loss, "grad_norm": gnorm, "overflow": overflow,
                       "loss_scale": new_ls.loss_scale,
                       # explicit name: gnorm here is the compressed-momentum
                       # norm, not a gradient norm (see _post_step)
                       "compressed_update_norm": gnorm}
            return new_state, (ew, es), metrics

        self._onebit_step_fn = jax.jit(step, donate_argnums=(0, 1))

    def _jit_train_steps(self, train_step):
        """N optimizer steps per dispatch: scan ``train_step`` over a
        leading steps axis of device-resident batches. The idiomatic TPU
        training loop (host dispatch + per-step host sync cost amortizes
        over N) — the reference has no analog because torch re-enters
        Python every step by construction. Shared by the fused engine and
        the pipeline engine (``train_batches`` contract: per-step RNG
        derives from one split; metrics stack along the steps axis)."""
        mesh = self.mesh

        def train_steps(state: TrainState, batches, rng):
            keys = jax.random.split(rng, jax.tree.leaves(batches)[0].shape[0])

            def body(st, xs):
                b, key = xs
                return train_step(st, b, key)

            return jax.lax.scan(body, state, (batches, keys))

        return jax.jit(
            train_steps,
            in_shardings=(self.state_shardings, None, NamedSharding(mesh, P())),
            out_shardings=(self.state_shardings, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

    def _build_offload_step_fns(self, grad_shardings):
        """Device side of the offload path: fwd+bwd+unscale+clip only; the
        fp32 master update happens on host. Under fp16 the live dynamic
        loss scale rides in as an argument — ``_accumulate_grads`` scales
        the loss and divides the gradients back down ON DEVICE (reference
        ``stage_1_and_2.py:1086`` unscale-and-clip), so host masters never
        see a scaled gradient and the overflow flag travels with the
        grads."""
        gas = self.config.gradient_accumulation_steps
        clip = self.config.gradient_clipping
        mesh = self.mesh
        fp16 = self._fp16_mode

        def grads_only(params, batch, rng, scale):
            return self._accumulate_grads(params, batch, rng, scale, grad_shardings,
                                          gas, clip, fp16=fp16)

        repl = NamedSharding(mesh, P())
        if getattr(self, "_param_offload_enabled", False):
            # ZeRO-Infinity full combo (param + optimizer offload): params
            # rest on host and stream through the grads pass; outputs keep
            # propagated shardings (explicit out_shardings on host-derived
            # values trip the SPMD partitioner — _accumulate_grads constrains
            # the grads in-graph)
            self._grads_only_fn = jax.jit(
                grads_only,
                in_shardings=(self.state_shardings.params, None, repl, repl))
        else:
            self._grads_only_fn = jax.jit(
                grads_only,
                in_shardings=(self.state_shardings.params, None, repl, repl),
                out_shardings=(repl, grad_shardings, repl, repl))

    def _setup_offload_optimizer(self):
        off = self.config.zero_config.offload_optimizer
        self._host_opt = None
        if off is None or getattr(off, "device", "none") in (None, "none"):
            return
        device = off.device if isinstance(off.device, str) else str(off.device)
        params = dict(self.config.optimizer_params or {})
        lr = params.get("lr", 1e-3)
        betas = tuple(params.get("betas", (0.9, 0.999)))
        eps = params.get("eps", 1e-8)
        wd = params.get("weight_decay", 0.0)
        adamw = (self.config.optimizer_name or C.ADAM_OPTIMIZER) == C.ADAMW_OPTIMIZER
        if device == "cpu":
            from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
            self._host_opt = DeepSpeedCPUAdam(lr=lr, betas=betas, eps=eps, weight_decay=wd,
                                              adamw_mode=adamw)
        elif device == "nvme":
            from deepspeed_tpu.runtime.swap_tensor.optimizer_swapper import NVMeAdam
            nvme_path = getattr(off, "nvme_path", None) or "/tmp/ds_tpu_nvme"
            # per-process swap dir: moment files are per-master-shard; two
            # processes sharing optimizer/ would overwrite each other's
            # exp_avg_*.bin (same reason as the params_proc<i> dirs)
            opt_dir = (f"optimizer_proc{jax.process_index()}"
                       if jax.process_count() > 1 else "optimizer")
            self._host_opt = NVMeAdam(swap_dir=os.path.join(str(nvme_path), opt_dir),
                                      lr=lr, betas=betas, eps=eps, weight_decay=wd, adamw_mode=adamw)
        else:
            raise ValueError(f"unknown offload_optimizer.device {device!r}")
        # fp32 host masters (reference: fp32 flat master partitions in host
        # RAM, per rank — stage_1_and_2.py:1086). Single-host: one master
        # per leaf (the reference's per-node footprint). Multi-host: SHARD
        # granularity — each process holds masters only for its unique
        # addressable shards and updates only those, exactly the
        # reference's per-rank partition model. Replicated leaves update
        # identically on every process (the host Adam is deterministic),
        # so no cross-host sync is needed.
        self._host_shard_mode = jax.process_count() > 1
        self._host_masters = self._build_host_masters()
        log_dist(f"optimizer offload enabled: device={device} "
                 f"({sum(m.size for m in self._host_masters) / 1e6:.1f}M host master elems"
                 + (", per-process shard partition" if self._host_shard_mode else "") + ")")

    def _build_host_masters(self):
        """fp32 host masters from the current params: whole leaves on a
        single process, this process's unique shards (param-sharding
        partition) in multi-host shard mode."""
        if getattr(self, "_host_shard_mode", False):
            from deepspeed_tpu.runtime.zero.param_offload import local_shard_arrays
            return [np.ascontiguousarray(np.asarray(a, np.float32))
                    for a in local_shard_arrays(jax.tree.leaves(self.state.params))]
        return [np.ascontiguousarray(np.asarray(jax.device_get(p), np.float32))
                for p in jax.tree.leaves(self.state.params)]

    def _offload_train_batch(self, device_batch, rng):
        """fwd+bwd on device (jitted), optimizer update on host via the C++
        kernel (reference async_accumulate_grad_in_cpu_via_gpu +
        cpu_adam path, stage_1_and_2.py:1086). fp16: the device program
        consumed the live dynamic scale and already unscaled the grads;
        an overflow step skips the host update and cuts the scale through
        the same loss-scaler state machine as the fused path."""
        self._ensure_params_resident()
        scale = (self.state.loss_scale.loss_scale if self._fp16_mode
                 else jnp.float32(1.0))
        loss, grads, gnorm, overflow = self._grads_only_fn(
            self.state.params, device_batch, rng, scale)
        if bool(overflow):
            new_ls = self._ls_update(self.state.loss_scale, jnp.asarray(True))
            self.state = self.state._replace(loss_scale=new_ls, step=self.state.step + 1)
            return loss, {"loss": loss, "grad_norm": gnorm, "overflow": jnp.asarray(True),
                          "loss_scale": new_ls.loss_scale}
        leaves, treedef = jax.tree.flatten(self.state.params)
        shard_leaves = jax.tree.leaves(self.state_shardings.params)
        grad_dev = jax.tree.leaves(grads)
        if getattr(self, "_host_shard_mode", False):
            with self.telemetry.span("optimizer_host"):
                return self._offload_step_sharded(loss, gnorm, leaves, treedef,
                                                  shard_leaves, grad_dev)
        new_leaves = [None] * len(leaves)
        with self.telemetry.span("optimizer_host"):
            if hasattr(self._host_opt, "step_single"):
                # pipelined: d2h of leaf i+1 overlaps the AVX update of leaf i
                # (the ctypes call releases the GIL); the h2d re-upload of leaf i
                # is async dispatch. Reference overlaps the same three stages
                # with CUDA streams (stage_1_and_2.py:1086).
                if not hasattr(self, "_offload_pool"):
                    import concurrent.futures
                    self._offload_pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
                fetch = lambda i: np.asarray(jax.device_get(grad_dev[i]), np.float32)
                self._host_opt.begin_step(lr=self.get_lr()[0])
                fut = self._offload_pool.submit(fetch, 0)
                for i, (m, old, s) in enumerate(zip(self._host_masters, leaves, shard_leaves)):
                    g = fut.result()
                    if i + 1 < len(leaves):
                        fut = self._offload_pool.submit(fetch, i + 1)
                    self._host_opt.step_single(i, m, g)
                    new_leaves[i] = jax.device_put(m.reshape(old.shape).astype(old.dtype), s)  # graft-lint: waive R008 offload params never donated (grads-only fn has no donate_argnums)
            else:
                grad_leaves = [np.asarray(jax.device_get(g), np.float32) for g in grad_dev]
                self._host_opt.step(self._host_masters, grad_leaves, lr=self.get_lr()[0])
                new_leaves = [jax.device_put(m.reshape(old.shape).astype(old.dtype), s)  # graft-lint: waive R008 offload params never donated (grads-only fn has no donate_argnums)
                              for m, old, s in zip(self._host_masters, leaves, shard_leaves)]
        new_params = jax.tree.unflatten(treedef, new_leaves)
        new_ls = self._ls_update(self.state.loss_scale, jnp.asarray(False))
        self.state = TrainState(step=self.state.step + 1, params=new_params,
                                opt_state=self.state.opt_state, loss_scale=new_ls)
        self._journal_params_to_nvme()
        return loss, {"loss": loss, "grad_norm": gnorm, "overflow": jnp.asarray(False),
                      "loss_scale": new_ls.loss_scale}

    def _offload_step_sharded(self, loss, gnorm, leaves, treedef, shard_leaves,
                              grad_dev):
        """Multi-host host-optimizer step at SHARD granularity: fetch only
        this process's unique grad shards, step the matching shard masters
        (same flat leaf-order x sorted-index order as ``local_shard_arrays``),
        rebuild the global params via per-device puts. The reference runs
        one swapper/optimizer per rank on its own partition
        (``stage_1_and_2.py:1086``); this is the jax.Array analog."""
        from deepspeed_tpu.runtime.zero.param_offload import (
            assemble_from_local_shards, local_shard_entries, _index_key)

        grad_shards = []
        for g, sh in zip(grad_dev, shard_leaves):
            by_key = {_index_key(s.index): s for s in g.addressable_shards}
            # enumerate by the PARAM sharding: masters were partitioned by
            # it, and _build_step_fns constrained the grads-only program's
            # outputs to the same layout
            for key, _idx, _devs in local_shard_entries(sh, g.shape):
                if key not in by_key:
                    raise RuntimeError(
                        f"grad shard layout {sorted(by_key)} does not cover the "
                        f"param shard partition key {key} — the grads-only "
                        f"program must emit grads in the params' layout "
                        f"(engine._build_step_fns shard-mode branch)")
                grad_shards.append(by_key[key])
        assert len(grad_shards) == len(self._host_masters), (
            len(grad_shards), len(self._host_masters))
        fetch = lambda i: np.asarray(grad_shards[i].data, np.float32)  # noqa: E731
        if hasattr(self._host_opt, "step_single"):
            if not hasattr(self, "_offload_pool"):
                import concurrent.futures
                self._offload_pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
            self._host_opt.begin_step(lr=self.get_lr()[0])
            fut = self._offload_pool.submit(fetch, 0)
            for i, m in enumerate(self._host_masters):
                g = fut.result()
                if i + 1 < len(self._host_masters):
                    fut = self._offload_pool.submit(fetch, i + 1)
                self._host_opt.step_single(i, m, g)
        else:
            self._host_opt.step(self._host_masters,
                                [fetch(i) for i in range(len(self._host_masters))],
                                lr=self.get_lr()[0])
        metas = [(tuple(l.shape), l.dtype) for l in leaves]
        new_leaves = assemble_from_local_shards(metas, shard_leaves,
                                                self._host_masters)
        new_params = jax.tree.unflatten(treedef, new_leaves)
        new_ls = self._ls_update(self.state.loss_scale, jnp.asarray(False))
        self.state = TrainState(step=self.state.step + 1, params=new_params,
                                opt_state=self.state.opt_state, loss_scale=new_ls)
        self._journal_params_to_nvme()
        return loss, {"loss": loss, "grad_norm": gnorm, "overflow": jnp.asarray(False),
                      "loss_scale": new_ls.loss_scale}

    def _setup_param_offload(self):
        """offload_param residency backends (param_offload.py). cpu: the
        pinned-host resting placement set up by the plan is the whole story.
        nvme: additionally journal every leaf to O_DIRECT files via the
        PartitionedParamSwapper (reference AsyncPartitionedParameterSwapper,
        ``partitioned_param_swapper.py:403``), keeping a ``max_in_cpu``-
        bounded window resident between steps."""
        self._param_swapper = None
        if not getattr(self, "_param_offload_enabled", False):
            return
        poff = self.config.zero_config.offload_param
        device = poff.device if isinstance(poff.device, str) else str(poff.device)
        if device == "nvme":
            from deepspeed_tpu.runtime.zero.param_offload import (
                PartitionedParamSwapper, local_shard_arrays)
            nvme_path = getattr(poff, "nvme_path", None) or "/tmp/ds_tpu_nvme"
            # per-host swap dir + host-local shard ownership: each process
            # journals only the unique addressable shards of every leaf —
            # the reference's per-rank swapper model
            # (partitioned_param_swapper.py:403). The proc suffix keeps
            # per-host files distinct even when nvme_path is a shared mount.
            swap_dir = (os.path.join(str(nvme_path), f"params_proc{jax.process_index()}")
                        if jax.process_count() > 1
                        else os.path.join(str(nvme_path), "params"))
            self._param_swapper = PartitionedParamSwapper(
                swap_dir,
                window_bytes=int(getattr(poff, "max_in_cpu", 1e9)),
                n_threads=max(int(getattr(poff, "buffer_count", 5)), 1))
            param_leaves = jax.tree.leaves(self.state.params)
            self._param_leaf_meta = [(tuple(l.shape), l.dtype) for l in param_leaves]
            self._param_swapper.initialize(local_shard_arrays(param_leaves))
        n_bytes = sum(int(np.prod(jnp.shape(l))) * jnp.asarray(l).dtype.itemsize
                      for l in jax.tree.leaves(self.state.params))
        log_dist(f"parameter offload enabled: device={device} "
                 f"({n_bytes / 1e6:.1f} MB resting off-HBM)")

    def _param_offload_train_batch(self, device_batch, rng):
        """One step of the streamed-parameter path: host params in, device
        shard outputs out, async d2h home (the out-of-graph half of
        param_offload.py's loop), NVMe journal when configured."""
        self._ensure_params_resident()
        rest = (self.state.step, self.state.opt_state, self.state.loss_scale)
        new_params_dev, new_rest, metrics = self._train_step_fn(
            self.state.params, rest, device_batch, rng)
        from deepspeed_tpu.runtime.zero.param_offload import migrate
        params_host = migrate(new_params_dev, self.state_shardings.params)
        self.state = TrainState(step=new_rest[0], params=params_host,
                                opt_state=new_rest[1], loss_scale=new_rest[2])
        self._journal_params_to_nvme()
        return metrics

    def _journal_params_to_nvme(self):
        """nvme tier post-step: persist updated leaves to the swap files and
        release the full pinned-host copy — between steps, host RAM holds
        only the swapper's ``max_in_cpu`` window (reference steady-state
        contract, ``partitioned_param_swapper.py``); the next consumer
        rematerializes via :meth:`_ensure_params_resident`."""
        if self._param_swapper is None:
            return
        from deepspeed_tpu.runtime.zero.param_offload import local_shard_arrays
        leaves = jax.tree.leaves(self.state.params)
        self._param_leaf_meta = [(tuple(l.shape), l.dtype) for l in leaves]
        self._param_swapper.write_back(local_shard_arrays(leaves))
        self._params_treedef = jax.tree.structure(self.state.params)
        self._params_released = True
        self.state = self.state._replace(params=None)

    def _ensure_params_resident(self):
        """Rebuild host-resident params from the NVMe journal if the last
        step released them (pipelined disk reads, window leaves from RAM)."""
        if not getattr(self, "_params_released", False):
            return
        from deepspeed_tpu.runtime.zero.param_offload import assemble_from_local_shards
        datas = self._param_swapper.fetch_all()
        leaves = assemble_from_local_shards(
            self._param_leaf_meta,
            jax.tree.leaves(self.state_shardings.params,
                            is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)),
            datas)
        self.state = self.state._replace(
            params=jax.tree.unflatten(self._params_treedef, leaves))
        self._params_released = False

    def _example_ids(self, batch):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        if ids.ndim == 3:  # [gas, micro, seq]
            ids = ids[0]
        return jnp.zeros((1, ids.shape[-1]), jnp.int32)

    # ------------------------------------------------------------------
    # jitted step construction
    # ------------------------------------------------------------------
    def _module_kwargs(self, mb):
        """Forward batch-dict keys that the module's signature accepts
        (attention_mask, token_type_ids, ...) alongside input_ids."""
        if not isinstance(mb, dict):
            return {}
        import inspect
        try:
            sig = inspect.signature(type(self.module).__call__)
        except (TypeError, ValueError):
            return {}
        return {k: v for k, v in mb.items() if k not in ("input_ids", "labels") and k in sig.parameters}

    def _quantize_gathered_weights(self, params):
        """ZeRO++ ``zero_quantized_weights`` numerics: the fsdp-sharded
        params are all-gathered through an int8 QDQ (reference quantized
        weight all-gather, ``partition_parameters.py:628`` ``CUDAQuantizer``;
        per-output-channel groups)."""
        from deepspeed_tpu.ops.quantizer import fake_quantize

        def qdq(p):
            if not jnp.issubdtype(p.dtype, jnp.floating) or p.ndim < 2:
                return p
            # per-output-channel groups (reference CUDAQuantizer per-channel
            # scales): flax kernels put the reduction dim first, so one group
            # = one trailing-axes element's column of length shape[0]. For a
            # DenseGeneral qkv kernel [in, 3, heads, head_dim] that is a
            # separate scale per (proj, head, channel) — never mixing heads
            # or q/k/v in one group.
            pt = jnp.moveaxis(p, 0, -1)  # [out..., in] — groups contiguous in memory
            q = fake_quantize(pt, num_bits=8, num_groups=pt.size // pt.shape[-1])
            q = jnp.moveaxis(q, -1, 0)
            # straight-through estimator: quantization error is outside the
            # gradient path (the reference quantizes the all-gather payload
            # outside autograd — identity gradient)
            return p + jax.lax.stop_gradient(q - p)

        return jax.tree.map(qdq, params)

    def _quantize_reduced_grads(self, grads, key):
        """ZeRO++ ``zero_quantized_gradients`` (qgZ) numerics: gradients pass
        through the two-hop quantized reduction's int8→int4 QDQ with
        stochastic rounding (reference ``all_to_all_quant_reduce``,
        ``runtime/comm/coalesced_collectives.py:31``). Communication itself
        rides the sharding constraint; this applies the matching precision
        loss so convergence behavior is faithful."""
        from deepspeed_tpu.ops.quantizer import fake_quantize

        from deepspeed_tpu.ops.quantizer.core import divisor_groups

        def qdq(path_leaf):
            i, g = path_leaf
            if not jnp.issubdtype(g.dtype, jnp.floating):
                return g
            groups = divisor_groups(g.size, 2048)
            k = jax.random.fold_in(key, i)
            return fake_quantize(g, num_bits=4, num_groups=groups,
                                 stochastic_rounding=True, rng=k)

        leaves, treedef = jax.tree.flatten(grads)
        return jax.tree.unflatten(treedef, [qdq((i, g)) for i, g in enumerate(leaves)])

    def _step_count_names(self):
        """The names of the counts the module's layers make on the device in
        a training step (``module.step_count_names()``; none for most), which
        the fused step then returns beside its loss."""
        if getattr(self, "_use_qcomm", False) or self._kd_config is not None:
            # those steps trace a loss of their own (quantized collectives;
            # distillation's captured forward), which returns no counts
            return ()
        names = getattr(self.module, "step_count_names", None)
        return tuple(names()) if callable(names) else ()

    def _record_step_counts(self, counts):
        """One step's device-side counts into the program's recorder: each
        name summed over the layers as a counter ``moe_<name>``, and a layer
        at a time as ring records ``count:moe_<name>`` (the count in ``uid``,
        ``layer_<i>`` in ``kind``). The step is over (the timer's sync has
        returned), so the read waits for nothing."""
        per_layer = np.asarray(jax.device_get(counts)).sum(axis=0)     # [layers, counts]
        self._last_step_counts = per_layer      # the monitor's, at its cadence
        rec, now = trace.recorder(), time.perf_counter()
        for at, name in enumerate(self._step_count_names()):
            rec.count("moe_" + name, int(per_layer[:, at].sum()))
            for layer, n in enumerate(per_layer[:, at]):
                rec.record("count:moe_" + name, now, now, int(n), kind=f"layer_{layer}")

    def _loss_for(self, params, mb, key, scale, train: bool = True, with_counts: bool = False):
        if getattr(self, "_param_offload_enabled", False):
            # ZeRO-Infinity param streaming: non-block leaves h2d here; block
            # subtrees pass through as host references and self-stream inside
            # their remat region (maybe_remat -> stream_block_params), so
            # backward re-streams per layer. The compute-dtype cast rides the
            # transfer (host-space leaves cannot be cast in place).
            from deepspeed_tpu.runtime.zero.param_offload import param_streaming, stream_tree
            with param_streaming(cast_dtype=self.compute_dtype):
                params = stream_tree(
                    params, skip_prefixes=getattr(self.module, "streamed_block_prefixes", ()))
                return self._loss_for_impl(params, mb, key, scale, train, precast=True,
                                           with_counts=with_counts)
        return self._loss_for_impl(params, mb, key, scale, train, with_counts=with_counts)

    def _loss_for_impl(self, params, mb, key, scale, train: bool = True, precast: bool = False,
                       with_counts: bool = False):
        """``with_counts`` (the fused train step, a module that names step
        counts): the forward also returns what its layers counted on the
        device this micro-batch (``moe/sharded_moe.py`` ``STEP_COUNTS``), as
        ``[layers, counts]`` int32 beside the loss: the aux is then
        ``(loss, counts)``."""
        if self.config.zero_config.zero_quantized_weights and not getattr(self, "_qcomm_tracing", False):
            # QDQ numerics apply everywhere EXCEPT inside the qcomm trace,
            # where the gather itself carries the int8 payload
            # (qcomm.quantized_allgather) — the forward/backward shim path
            # keeps its QDQ weight numerics either way
            params = self._quantize_gathered_weights(params)
        cparams = params if precast else _cast_floating(params, self.compute_dtype)
        ids = mb["input_ids"] if isinstance(mb, dict) else mb
        extra = self._module_kwargs(mb)
        mcfg = getattr(self.module, "config", None)
        has_dropout = mcfg is not None and getattr(mcfg, "dropout", 0.0) > 0.0
        has_moe = mcfg is not None and getattr(mcfg, "moe_num_experts", 0) > 0
        # fused-head models compute the loss inside apply() (no [B,L,V]
        # logits); only the default loss path knows that contract
        fused_head = (self.loss_fn is default_causal_lm_loss and mcfg is not None
                      and getattr(mcfg, "fused_head_loss_chunk", 0) > 0)
        if fused_head:
            extra = dict(extra,
                         labels=mb.get("labels", ids) if isinstance(mb, dict) else mb)
        has_pld = "pld_theta" in extra  # only set when the module accepts it
        kd = self._kd_config if train else None
        want_caps = (kd is not None and not fused_head
                     and float(kd.get("layerwise_coef", 0.0)) > 0.0)
        caps = None
        if train and (has_dropout or has_moe or has_pld):
            # 2-way split preserved when PLD is off: existing dropout/gating
            # rng streams are a reproducibility contract
            if has_pld:
                drop_key, gate_key, pld_key = jax.random.split(key, 3)
                rngs = {"dropout": drop_key, "gating": gate_key, "pld": pld_key}
            else:
                drop_key, gate_key = jax.random.split(key)
                rngs = {"dropout": drop_key, "gating": gate_key}
            if want_caps:
                outputs, ivars = self.module.apply(
                    {"params": cparams}, ids, deterministic=False, rngs=rngs,
                    capture_intermediates=self._kd_block_filter(), **extra)
                caps = ivars["intermediates"]
            elif with_counts:
                from deepspeed_tpu.moe.sharded_moe import STEP_COUNTS
                outputs, counted = self.module.apply({"params": cparams}, ids, deterministic=False,
                                                     rngs=rngs, mutable=[STEP_COUNTS], **extra)
                counts = self.module.step_counts(counted[STEP_COUNTS])     # [layers, counts]
            else:
                outputs = self.module.apply({"params": cparams}, ids, deterministic=False,
                                            rngs=rngs, **extra)
        elif want_caps:
            # train without stochastic layers (dropout/moe/pld all off):
            # deterministic apply, but layerwise KD still needs the captures
            outputs, ivars = self.module.apply(
                {"params": cparams}, ids, deterministic=True,
                capture_intermediates=self._kd_block_filter(), **extra)
            caps = ivars["intermediates"]
        else:
            # eval: deterministic gating (eval capacity factor, no RTS/noise);
            # the aux loss is a training-only regularizer — report pure CE
            outputs = self.module.apply({"params": cparams}, ids, deterministic=True, **extra)
            if has_moe and isinstance(outputs, (tuple, list)):
                outputs = outputs[0]
        loss = outputs if fused_head else self.loss_fn(outputs, mb)
        if kd is not None:
            if fused_head:
                raise ValueError("knowledge_distillation needs student logits; "
                                 "fused_head_loss_chunk never materializes them — "
                                 "disable one of the two")
            loss = self._apply_kd(loss, outputs, ids, mb, caps, extra)
        if with_counts:
            return (loss * scale).astype(jnp.float32), (loss, counts)
        return (loss * scale).astype(jnp.float32), loss

    def _maybe_apply_student_init(self):
        """Consume a staged layer_reduction seed (single implementation for
        both the init_compression-after-state and initialize_state orders)."""
        if self._pending_student_init is None or self.state is None:
            return
        from deepspeed_tpu.compression.compress import student_initialization
        t_params, raw = self._pending_student_init
        new = student_initialization(jax.device_get(self.state.params),
                                     jax.device_get(t_params), raw)
        # owned copy: the host-built tree enters the DONATED train step; a
        # zero-copy device_put would hand XLA foreign memory to free
        # (utils/device.py)
        from deepspeed_tpu.utils.device import owned_device_put
        self.state = self.state._replace(
            params=owned_device_put(new, self.state_shardings.params))
        self._pending_student_init = None

    def _kd_block_filter(self, module=None):
        """flax capture_intermediates filter selecting transformer blocks by
        name (``h_3``/``layers_3``/...). Prefixes come from the KD config's
        ``block_prefix`` override, else from the TARGET module's own
        ``streamed_block_prefixes`` — the teacher's naming may differ from
        the student's (GPT-2 ``h_`` vs LLaMA ``layers_``)."""
        import re
        kd = self._kd_config
        prefixes = kd.get("block_prefix")
        if prefixes is None:
            prefixes = getattr(module if module is not None else self.module,
                               "streamed_block_prefixes", ("h_",))
        if isinstance(prefixes, str):
            prefixes = (prefixes,)
        pats = [re.compile(re.escape(p) + r"\d+") for p in prefixes]

        def filt(mdl, method_name):
            name = getattr(mdl, "name", None) or ""
            return method_name == "__call__" and any(p.fullmatch(name) for p in pats)

        return filt

    @staticmethod
    def _kd_hidden(caps, name):
        """Block output from a capture tree: the first __call__'s return,
        unwrapping (x, aux) block tuples to the hidden state."""
        entry = caps[name]["__call__"][0]
        return entry[0] if isinstance(entry, (tuple, list)) else entry

    def _apply_kd(self, ce_loss, outputs, ids, mb, student_caps, extra_kwargs):
        """Staged knowledge distillation (reference role: SLW scheduler
        ``compression/scheduler.py`` + the KD losses its example training
        scripts compute around ``init_compression``'s teacher). The teacher
        forward runs IN-GRAPH under stop_gradient and under ``lax.cond`` on
        the schedule gate — outside [schedule_offset, schedule_offset_end)
        the loss is exactly CE and the teacher FLOPs are skipped. The logit
        term is Hinton KL at temperature T (scaled T^2); the layerwise term
        an MSE between matched block hiddens (student layer i vs teacher
        layer ``teacher_layer[i]`` when layer_reduction maps them, else the
        teacher's i-th block): loss = (1-a)·CE + a·KL + gate·lw·MSE with
        a = kd_coef·gate.

        Teacher placement: init_compression shards the teacher over the
        engine's mesh with the planner's rules (compress._place_teacher),
        so its weights rest 1/fsdp per chip and ride the trace as sharded
        constants; exotic teacher structures fall back to host constants
        (replicated)."""
        kd = self._kd_config
        t_module, t_params = kd["module"], kd["params"]
        step = mb.get("_kd_step") if isinstance(mb, dict) else None
        if step is None:
            # paths without in-graph step injection (shims) run pure CE
            return ce_loss
        step = jnp.asarray(step)
        gate_on = ((step >= int(kd["schedule_offset"]))
                   & (step < int(kd["schedule_offset_end"])))
        want_caps = student_caps is not None
        T = float(kd.get("temperature", 2.0))
        lw = float(kd.get("layerwise_coef", 0.0))
        kd_coef = float(kd.get("kd_coef", 0.5))
        s_logits = outputs[0] if isinstance(outputs, (tuple, list)) else outputs

        def kd_terms(_):
            t_vars = {"params": jax.tree.map(jnp.asarray, t_params)}
            t_kwargs = {k: v for k, v in (extra_kwargs or {}).items()
                        if k in self._module_kwargs_names(t_module)}
            if want_caps:
                t_out, t_ivars = t_module.apply(
                    t_vars, ids, deterministic=True,
                    capture_intermediates=self._kd_block_filter(t_module), **t_kwargs)
                t_caps = jax.lax.stop_gradient(t_ivars["intermediates"])
            else:
                t_out = t_module.apply(t_vars, ids, deterministic=True, **t_kwargs)
            t_logits = t_out[0] if isinstance(t_out, (tuple, list)) else t_out
            t_logits = jax.lax.stop_gradient(t_logits).astype(jnp.float32)
            s = s_logits.astype(jnp.float32) / T
            t = t_logits / T
            t_prob = jax.nn.softmax(t, axis=-1)
            kl = jnp.sum(t_prob * (jax.nn.log_softmax(t, axis=-1)
                                   - jax.nn.log_softmax(s, axis=-1)), axis=-1)
            kd_kl = jnp.mean(kl) * (T * T)
            mse = jnp.float32(0.0)
            if lw > 0.0 and want_caps:
                from deepspeed_tpu.compression.config import (LAYER_REDUCTION,
                                                              get_compression_config)
                lr = get_compression_config(self._compression_config or {})[LAYER_REDUCTION]
                s_names = sorted(student_caps.keys(),
                                 key=lambda n: int(n.rsplit("_", 1)[-1]))
                t_sorted = sorted(t_caps.keys(), key=lambda n: int(n.rsplit("_", 1)[-1]))
                if lr.get("enabled", False) and lr.get("teacher_layer"):
                    # indices into the TEACHER'S OWN block list (its prefix
                    # may differ from the student's)
                    idxs = [int(i) for i in lr["teacher_layer"]][:len(s_names)]
                    t_names = [t_sorted[i] for i in idxs]
                else:
                    if len(t_sorted) < len(s_names):
                        raise ValueError(
                            f"layerwise KD: teacher has {len(t_sorted)} blocks for "
                            f"{len(s_names)} student blocks and no layer_reduction "
                            f"teacher_layer mapping; provide one")
                    t_names = t_sorted[:len(s_names)]
                for s_name, t_name in zip(s_names, t_names):
                    hs = self._kd_hidden(student_caps, s_name).astype(jnp.float32)
                    ht = self._kd_hidden(t_caps, t_name).astype(jnp.float32)
                    mse = mse + jnp.mean(jnp.square(hs - ht))
                mse = mse / max(len(s_names), 1)
            return ((1.0 - kd_coef) * ce_loss + kd_coef * kd_kl
                    + jnp.float32(lw) * mse).astype(jnp.float32)

        return jax.lax.cond(gate_on, kd_terms,
                            lambda _: ce_loss.astype(jnp.float32), operand=None)

    @staticmethod
    def _module_kwargs_names(module):
        import inspect
        try:
            return set(inspect.signature(type(module).__call__).parameters)
        except (TypeError, ValueError):
            return set()

    def _moq_eigenvalue_factors(self):
        """Eigenvalue-modulated MoQ periods (reference ``engine.py`` wires
        ``Eigenvalue`` into the quantizer at GAS boundaries; the TPU
        schedule is compiled in-graph, so curvature is probed ONCE here on
        a synthetic batch and baked in as per-layer period factors —
        ``1 + floor(eig/max_eig * 4)``, high-curvature layers anneal
        slower). Returns None unless the ``eigenvalue`` config block is
        enabled alongside quantize_training."""
        ev_cfg = (self.config.raw_dict or {}).get("eigenvalue", {})
        if not ev_cfg.get("enabled", False):
            return None
        import math

        from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

        mcfg = getattr(self.module, "config", None)
        layer_name = ev_cfg.get("layer_name", "h")
        layer_num = int(ev_cfg.get("layer_num",
                                   getattr(mcfg, "n_layer",
                                           getattr(mcfg, "num_hidden_layers", 0))))
        if not layer_name or layer_num <= 0:
            logger.warning("eigenvalue enabled but layer_name/layer_num resolve to "
                           f"{layer_name!r}/{layer_num}; skipping MoQ period modulation")
            return None
        seq = min(int(getattr(mcfg, "n_positions",
                              getattr(mcfg, "max_position_embeddings", 128))), 128)
        vocab = int(getattr(mcfg, "vocab_size", 256))
        rng = np.random.default_rng(0)
        probe = {"input_ids": rng.integers(
            0, vocab, (self.config.train_micro_batch_size_per_gpu, seq)).astype(np.int32)}

        def loss_fn(p):
            _, loss = self._loss_for(p, probe, jax.random.PRNGKey(0),
                                     jnp.float32(1.0), train=False)
            return loss

        ev = Eigenvalue(verbose=bool(ev_cfg.get("verbose", False)),
                        max_iter=int(ev_cfg.get("max_iter", 10)),
                        tol=float(ev_cfg.get("tol", 1e-2)),
                        stability=float(ev_cfg.get("stability", 1e-6)),
                        layer_name=layer_name, layer_num=layer_num)
        try:
            # raw values (scrub=False): a diverged layer must SKIP the
            # modulation, not inherit the max-curvature factor
            eigs = ev.compute_eigenvalue(loss_fn, self.state.params, scrub=False)
        except KeyError as e:
            logger.warning(f"eigenvalue: {e}; skipping MoQ period modulation")
            return None
        if not all(np.isfinite(e) for e in eigs):
            logger.warning("eigenvalue returned non-finite values; skipping MoQ "
                           "period modulation")
            return None
        max_eig = max(eigs) or 1.0
        factors = {f"{layer_name}_{i}": 1.0 + math.floor(e / max_eig * 4)
                   for i, e in enumerate(eigs)}
        log_dist(f"MoQ eigenvalue period factors: {factors}")
        return factors

    def _cond_apply_updates(self, overflow, grads, opt_state, params):
        """Optimizer update under an overflow gate: lax.cond runs ONE branch
        at runtime, so a skipped step costs nothing and a normal step avoids
        the full extra read+blend pass over params+optimizer state that a
        where-select would pay every step (~12 GB at 350M fp32 state).
        Shared by the fused, shim, and pipeline step builders so the skip
        semantics cannot drift."""

        def apply_branch(args):
            g, opt, p = args
            updates, new_opt = self.optimizer.update(g, opt, p)
            return optax.apply_updates(p, updates), new_opt

        def skip_branch(args):
            _, opt, p = args
            return p, opt

        return jax.lax.cond(overflow, skip_branch, apply_branch,
                            (grads, opt_state, params))

    def _build_step_fns(self):
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        clip = cfg.gradient_clipping
        fp16 = self._fp16_mode
        grad_shardings = self.plan.grad_shardings()

        # ZeRO++ quantized comm: real int8/int4 wire payloads need the
        # explicit shard_map path, which composes with pure-DP meshes only;
        # other topologies keep the QDQ numerics simulation
        zc = cfg.zero_config
        want_qcomm = bool(zc.zero_quantized_gradients or zc.zero_quantized_weights
                          or _comm_dtype(cfg) is not None)
        mcfg = getattr(self.module, "config", None)
        has_moe = mcfg is not None and getattr(mcfg, "moe_num_experts", 0) > 0
        # tensor axes compose: the qcomm shard_map is manual over (data,
        # fsdp) only and GSPMD keeps owning the TP collectives inside
        # (qcomm.py axis_names); pipe/expert/sequence still fall back
        # (TP composes through qcomm's partial-manual shard_map: tensor stays
        # an automatic axis)
        dp_compat = all(self.mesh.shape[a] == 1 for a in ("pipe", "sequence", "expert"))
        dp_world = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        self._use_qcomm = (want_qcomm and dp_compat and dp_world > 1
                           and not has_moe
                           and not getattr(self, "_offload_enabled", False)
                           and not getattr(self, "_param_offload_enabled", False))
        if want_qcomm and not self._use_qcomm:
            log_dist("explicit-wire communication requires a DP(+TP) mesh without "
                     "pipe/sequence/expert axes or MoE/offload; ZeRO++ quantized "
                     "configs fall back to QDQ numerics and communication_data_type "
                     "falls back to GSPMD default dtypes (no wire savings either way)")

        # 1-bit Adam compressed collective (reference compressed_allreduce,
        # runtime/comm/nccl.py:51): after freeze_step the DP exchange becomes
        # packed sign bits of the momentum — needs replicated params/opt
        # state (stage 0) on a pure-DP mesh
        # shared hyperparameter parsing for the compressed-comm optimizers:
        # the schedule (when configured) must keep driving the lr through
        # the compression phase
        def compressed_opt_params():
            op = dict(cfg.optimizer_params or {})
            return op, dict(
                lr=self.lr_scheduler if self.lr_scheduler is not None else op.get("lr", 1e-3),
                betas=tuple(op.get("betas", (0.9, 0.999))),
                eps=op.get("eps", 1e-8), weight_decay=op.get("weight_decay", 0.0))

        # (a rebuild, e.g. init_compression, must not zero live 1-bit error
        # feedback — __init__ owns the _onebit_errors default)
        self._onebit_cfg = None
        self._onebit_step_fn = None
        if (cfg.optimizer_name in (C.ONEBIT_ADAM_OPTIMIZER, C.ONEBIT_LAMB_OPTIMIZER)
                and self.client_optimizer is None):
            opt_label = "1-bit Adam" if cfg.optimizer_name == C.ONEBIT_ADAM_OPTIMIZER else "1-bit LAMB"
            if self._compressed_comm_eligible(cfg.optimizer_name):
                op, base = compressed_opt_params()
                self._onebit_cfg = dict(base,
                                        freeze_step=int(op.get("freeze_step", 100000)),
                                        mode=("lamb" if cfg.optimizer_name == C.ONEBIT_LAMB_OPTIMIZER
                                              else "adam"))
                log_dist(f"{opt_label} compressed collective active after "
                         f"freeze_step={self._onebit_cfg['freeze_step']} (1-bit wire payload)")
                if clip > 0:
                    log_dist(f"warning: gradient_clipping is not applied during the {opt_label} "
                             "compression phase (local gradients are never globally reduced; "
                             "matches reference 1-bit semantics)")
            else:
                log_dist(f"{opt_label} compressed collective requires a pure-DP mesh at "
                         "ZeRO stage 0; using error-feedback numerics without comm savings")

        # 0/1 Adam: the real interval/local-step schedule (runtime/zeroone.py).
        # A rebuild keeps the live runner — its buffers ARE optimizer state.
        if (self._zeroone_runner is None
                and cfg.optimizer_name == C.ZERO_ONE_ADAM_OPTIMIZER
                and self.client_optimizer is None
                and self._compressed_comm_eligible(C.ZERO_ONE_ADAM_OPTIMIZER)):
            from deepspeed_tpu.runtime.zeroone import ZeroOneRunner
            op, base = compressed_opt_params()
            zo_cfg = dict(
                base,
                var_freeze_step=int(op.get("var_freeze_step", 100000)),
                var_update_scaler=int(op.get("var_update_scaler", 16)),
                local_step_scaler=int(op.get("local_step_scaler", 32678)),
                local_step_clipper=int(op.get("local_step_clipper", 16)))
            self._zeroone_runner = ZeroOneRunner(self, zo_cfg)
            log_dist(f"0/1 Adam engine schedule active: var_freeze_step="
                     f"{zo_cfg['var_freeze_step']} (1-bit grad wire + collective-free "
                     f"local steps after freeze)")
            if clip > 0:
                log_dist("warning: gradient_clipping is not applied by the 0/1 Adam "
                         "schedule (local gradients are never globally reduced; matches "
                         "reference 0/1 Adam semantics)")
            if fp16:
                log_dist("warning: 0/1 Adam runs without dynamic loss scaling; "
                         "use bf16 or fp32 compute")
        elif cfg.optimizer_name == C.ZERO_ONE_ADAM_OPTIMIZER and self.client_optimizer is None:
            log_dist("0/1 Adam compressed schedule requires a pure-DP mesh at ZeRO "
                     "stage 0; using interval numerics without comm savings")
        mesh = self.mesh

        # compression-in-forward: resolve the config against the real param
        # tree once shapes are known (compression.init_compression)
        if self._compression_pending and self.state is not None:
            from deepspeed_tpu.compression.compress import build_compression_transform
            self._compression_transform = (
                build_compression_transform(self.state.params, self._compression_config)
                if self._compression_config is not None else None)
            # MoQ (quantize_training) chains after compression masks/quant —
            # both are (params, step) -> params transforms
            moq = None
            if self.config.quantize_training_config.get("enabled", False):
                from deepspeed_tpu.runtime.quantize import build_moq_transform
                moq = build_moq_transform(self.state.params,
                                          self.config.quantize_training_config,
                                          period_factors=self._moq_eigenvalue_factors())
            if moq is not None:
                comp = self._compression_transform
                self._compression_transform = (
                    moq if comp is None else (lambda p, s: moq(comp(p, s), s)))
            self._compression_pending = False
            if self._compression_transform is not None and self._use_qcomm:
                dropped = ("communication_data_type reductions"
                           if _comm_dtype(cfg) is not None else "quantized collectives")
                log_dist(f"warning: compression-in-forward does not compose with the "
                         f"qcomm shard_map path; disabling {dropped} "
                         f"(reductions run at GSPMD default dtypes)")
                self._use_qcomm = False
            if self._compression_transform is not None and (
                    getattr(self, "_offload_enabled", False)
                    or self._zeroone_runner is not None
                    or cfg.optimizer_name == C.ONEBIT_ADAM_OPTIMIZER):
                logger.warning("compression-in-forward only applies on the fused "
                               "train_batch path; offload/1-bit/0-1 Adam steps run "
                               "uncompressed")
            if self._compression_transform is not None and getattr(
                    self, "_param_offload_enabled", False):
                # the transform would run on pinned-host leaves before
                # _loss_for's streaming h2d — compute on host-space operands
                # fails at compile; fail here with the fix named
                raise ValueError("compression-in-forward does not compose with "
                                 "offload_param (masks/quantization would apply to "
                                 "host-resident leaves); disable one of the two")

        if getattr(self, "_offload_enabled", False):
            if getattr(self, "_host_shard_mode", False):
                # shard-granular host masters pair 1:1 with PARAM shards
                # (_offload_step_sharded): grads must leave the device
                # program in the params' layout, not the fsdp-everything
                # grad layout (a replicated-under-persistence-threshold
                # param would otherwise meet an fsdp-sharded grad and the
                # shard pairing would break)
                dev_param_shardings = jax.tree.map(
                    lambda s: NamedSharding(s.mesh, s.spec)
                    if isinstance(s, NamedSharding) else s,
                    self.state_shardings.params,
                    is_leaf=lambda x: isinstance(x, NamedSharding))
                self._build_offload_step_fns(dev_param_shardings)
            else:
                self._build_offload_step_fns(grad_shardings)

        def grads_of_micro(params, mb, key, scale):
            (scaled_loss, loss), grads = jax.value_and_grad(self._loss_for, has_aux=True)(params, mb, key, scale)
            grads = _cast_floating(grads, jnp.float32)
            return loss, grads

        def train_step(state: TrainState, batch, rng):
            scale = state.loss_scale.loss_scale if fp16 else jnp.float32(1.0)
            ctrans = self._compression_transform
            pt = (lambda p: ctrans(p, state.step)) if ctrans is not None else None
            extra = None
            if self.progressive_layer_drop is not None:
                # reference theta schedule, computed in-graph from the step
                # counter so the fused scan anneals without recompiles
                pld = self.progressive_layer_drop
                theta = ((1.0 - pld.theta) * jnp.exp(-pld.gamma * state.step.astype(jnp.float32))
                         + pld.theta)
                extra = {"pld_theta": theta}
            if self._kd_config is not None:
                # the KD schedule gate reads the live step counter in-graph
                # (same mechanism as the PLD theta — no retrace on activation)
                extra = dict(extra or {}, _kd_step=state.step)
            counted = {} if self._step_count_names() else None
            losses, grads, gnorm, overflow = self._accumulate_grads(
                state.params, batch, rng, scale, grad_shardings, gas, clip, fp16,
                params_transform=pt, model_extra=extra, step_counts=counted)
            if getattr(self, "_param_offload_enabled", False):
                # second touch of the step (reference optimizer-substep param
                # access): stream the host-resident masters in for the update
                # math; no compute-dtype cast — the update runs at param dtype
                from deepspeed_tpu.runtime.zero.param_offload import (param_streaming,
                                                                      stream_tree)
                with param_streaming():
                    state = state._replace(params=stream_tree(state.params))

            # overflow → skip update (reference stage step-skip semantics).
            # Applied in every dtype mode: for bf16/fp32 `overflow` is a
            # non-finite grad norm, and letting that update through would
            # poison the params while metrics claim the step was skipped
            # (the offload path already skips — keep the two paths agreeing).
            # lax.cond, NOT where-select: the select form computes the update
            # AND re-reads both old and new state for the blend — a full
            # extra pass over params+optimizer state (~12 GB at 350M fp32)
            # on EVERY step to serve an almost-never branch
            new_params, new_opt = self._cond_apply_updates(
                overflow, grads, state.opt_state, state.params)
            new_ls = self._ls_update(state.loss_scale, overflow)
            new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt,
                                   loss_scale=new_ls)
            metrics = {
                "loss": losses,
                "grad_norm": gnorm,
                "overflow": overflow,
                "loss_scale": new_ls.loss_scale,
            }
            if counted:
                metrics["step_counts"] = counted["counts"]
            return new_state, metrics

        # batch leaves keep the shardings _shard_batch placed them with (a
        # single broadcast spec would rank-mismatch scalar/per-sample leaves)
        if getattr(self, "_param_offload_enabled", False):
            # offload_param jit contract (param_offload.py): host-space
            # in_shardings for the resting params, NO out_shardings (this
            # XLA's SPMD partitioner cannot partition placement annotations
            # on non-parameters — updated params exit in device memory and
            # go home via a plain async device_put in _dispatch_train_step),
            # and donation only of the device-resident rest (params cannot
            # alias across memory kinds)
            def train_step_off(params, rest, batch, rng):
                state = TrainState(step=rest[0], params=params, opt_state=rest[1],
                                   loss_scale=rest[2])
                new_state, metrics = train_step(state, batch, rng)
                return (new_state.params,
                        (new_state.step, new_state.opt_state, new_state.loss_scale),
                        metrics)

            repl = NamedSharding(mesh, P())
            rest_shardings = (self.state_shardings.step, self.state_shardings.opt_state,
                              self.state_shardings.loss_scale)
            self._train_step_fn = jax.jit(
                train_step_off,
                in_shardings=(self.state_shardings.params, rest_shardings, None, repl),
                donate_argnums=(1,),
            )
        else:
            self._train_step_fn = jax.jit(
                train_step,
                in_shardings=(self.state_shardings, None, NamedSharding(mesh, P())),
                out_shardings=(self.state_shardings, NamedSharding(mesh, P())),
                donate_argnums=(0,),
            )

        if getattr(self, "_param_offload_enabled", False):
            # a scanned multi-step would carry params on device across the
            # whole scan — exactly the residency offload removes. train_batches
            # falls back to per-step dispatch (the host round-trip IS the point).
            self._train_steps_fn = None
        else:
            self._train_steps_fn = self._jit_train_steps(train_step)

        def eval_step(params, mb, step):
            # eval must score the same network training optimizes: the
            # compression transform (when installed) applies here too
            if self._compression_transform is not None:
                params = self._compression_transform(params, step)
            _, loss = self._loss_for(params, mb, jax.random.PRNGKey(0), jnp.float32(1.0), train=False)
            return loss

        if getattr(self, "_param_offload_enabled", False):
            # explicit out_shardings on host-derived values trip the SPMD
            # partitioner's placement-annotation handling; let the scalar
            # loss placement propagate
            self._eval_step_fn = jax.jit(eval_step,
                                         in_shardings=(self.state_shardings.params, None,
                                                       NamedSharding(mesh, P())))
        else:
            self._eval_step_fn = jax.jit(eval_step,
                                         in_shardings=(self.state_shardings.params, None,
                                                       NamedSharding(mesh, P())),
                                         out_shardings=NamedSharding(mesh, P()))

        # shim path: per-microbatch grads + deferred apply
        def micro_grads(params, mb, key, scale):
            return grads_of_micro(params, mb, key, scale)

        self._micro_grad_fn = jax.jit(micro_grads,
                                      in_shardings=(self.state_shardings.params, None,
                                                    NamedSharding(mesh, P()), NamedSharding(mesh, P())),
                                      out_shardings=(NamedSharding(mesh, P()), grad_shardings))

        def apply_grads(state, grads, n_micro):
            scale = state.loss_scale.loss_scale if fp16 else jnp.float32(1.0)
            grads = jax.tree.map(lambda g: g / (n_micro * scale), grads)
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            gnorm = _global_norm(grads)
            overflow = has_overflow(grads) if fp16 else ~jnp.isfinite(gnorm)
            if clip > 0:
                factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)
            new_params, new_opt = self._cond_apply_updates(
                overflow, grads, state.opt_state, state.params)
            new_ls = self._ls_update(state.loss_scale, overflow)
            new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt, loss_scale=new_ls)
            return new_state, {"grad_norm": gnorm, "overflow": overflow, "loss_scale": new_ls.loss_scale}

        self._apply_grads_fn = jax.jit(apply_grads,
                                       in_shardings=(self.state_shardings, grad_shardings),
                                       out_shardings=(self.state_shardings, NamedSharding(mesh, P())),
                                       donate_argnums=(0, 1),
                                       static_argnums=(2,))

    # ------------------------------------------------------------------
    # data plumbing
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, **kwargs):
        """Build the training dataloader (reference ``deepspeed_io``
        ``engine.py:1617``)."""
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        return DeepSpeedDataLoader(dataset,
                                   batch_size=batch_size or self.config.train_batch_size,
                                   collate_fn=collate_fn,
                                   drop_last=self.config.dataloader_drop_last,
                                   seed=self.config.seed)

    def _training_iterator(self):
        """Persistent iterator over the training dataloader (restarts across
        epochs)."""
        if self.training_dataloader is None:
            return None
        if getattr(self, "_train_iter", None) is None:
            from deepspeed_tpu.runtime.dataloader import RepeatingLoader
            self._train_iter = iter(RepeatingLoader(self.training_dataloader))
        return self._train_iter

    def _batch_spec(self, with_gas_dim: bool) -> P:
        """[gas?, batch, seq] spec: batch over the DP axes; the sequence dim
        additionally over the ``sequence`` axis when sequence parallelism is
        on (tokens then live sequence-sharded end to end — embedding lookup
        included — and ring/Ulysses attention keeps them that way)."""
        return self.topology.batch_spec(extra_leading=1 if with_gas_dim else 0,
                                        shard_sequence=self.topology.sequence_parallel_size > 1)

    def _shard_batch(self, batch, with_gas_dim: bool):
        """Global batch dict → device arrays with the batch sharded over the
        DP axes (and optionally reshaped to [gas, micro_global, ...])."""
        gas = self.config.gradient_accumulation_steps
        spec = self._batch_spec(with_gas_dim)

        def put(x):
            x = np.asarray(x)
            if with_gas_dim:
                b = x.shape[0]
                assert b % gas == 0, f"global batch {b} not divisible by GAS {gas}"
                x = x.reshape((gas, b // gas) + x.shape[1:])
            leaf_spec = P(*spec[:x.ndim])  # rank-1 leaves (e.g. weights) drop the seq part
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                return multihost_utils.host_local_array_to_global_array(x, self.mesh, leaf_spec)
            return jax.device_put(x, NamedSharding(self.mesh, leaf_spec))  # graft-lint: waive R008 batch staging, batches are never donated

        return jax.tree.map(put, batch)

    def _shard_batch_steps(self, batch_stack):
        """[n_steps, global_batch, ...] host leaves → device arrays shaped
        [n_steps, gas, micro_global, ...] with the batch dim over the DP axes."""
        gas = self.config.gradient_accumulation_steps
        spec = self.topology.batch_spec(extra_leading=2,
                                        shard_sequence=self.topology.sequence_parallel_size > 1)

        def put(x):
            x = np.asarray(x)
            n, b = x.shape[0], x.shape[1]
            assert b % gas == 0, f"global batch {b} not divisible by GAS {gas}"
            x = x.reshape((n, gas, b // gas) + x.shape[2:])
            leaf_spec = P(*spec[:x.ndim])
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                return multihost_utils.host_local_array_to_global_array(x, self.mesh, leaf_spec)
            return jax.device_put(x, NamedSharding(self.mesh, leaf_spec))  # graft-lint: waive R008 batch staging, batches are never donated

        return jax.tree.map(put, batch_stack)

    def train_batches(self, batch_stack):
        """Run ``n_steps`` full optimization steps in ONE device dispatch.

        ``batch_stack`` leaves are stacked host arrays
        ``[n_steps, global_batch, ...]``; the steps run as a ``lax.scan`` over
        the fused train step, so per-step host dispatch/sync cost amortizes
        over the whole stack — the idiomatic TPU training loop. (The
        reference has no analog: torch re-enters Python every step by
        construction.)

        Falls back to per-step ``train_batch`` when a host-driven schedule
        owns stepping (offload optimizer, 1-bit/0-1 Adam phase switching,
        curriculum seqlen, grad retention). Per-step RNG derives from one
        fold_in + split rather than per-step fold_in, so dropout/gating
        noise differs from an equivalent ``train_batch`` sequence (same
        distribution).

        Returns the per-step loss array ``[n_steps]``.
        """
        leaves = jax.tree.leaves(batch_stack)
        if not leaves or np.ndim(leaves[0]) < 2:
            raise ValueError("train_batches needs [n_steps, global_batch, ...] leaves")
        n_steps = np.shape(leaves[0])[0]
        host_paths = (getattr(self, "_host_opt", None) is not None
                      or getattr(self, "_param_offload_enabled", False)
                      or self._zeroone_runner is not None
                      or self._onebit_cfg is not None
                      or self.curriculum_scheduler is not None
                      or getattr(self, "_retain_grads_flag", False))
        if host_paths:
            losses = [self.train_batch(jax.tree.map(lambda x: np.asarray(x)[i], batch_stack))
                      for i in range(n_steps)]
            return jnp.stack([jnp.asarray(l) for l in losses])
        example = jax.tree.map(lambda x: np.asarray(x)[0], batch_stack)
        self._maybe_autotune(example)
        self.initialize_state(example)
        self._maybe_write_telemetry_header(example)
        self._maybe_trace_window(n_steps)
        tel = self.telemetry
        step_no = self.global_steps + 1
        tel.begin_step(step_no)
        with tel.span("train_batch", step_no, trace.UNIT) as unit:
            unit.kind = "stack"     # another program than a single step's: its first compile is no recompile
            with tel.span("timer_sync", step_no):
                self.tput_timer.start()
            self.timers(TRAIN_BATCH_TIMER).start()
            with tel.span("batch_stage", step_no):
                device_batch = self._shard_batch_steps(batch_stack)
            rng = jax.random.fold_in(self._base_rng, self.global_steps)
            with tel.span("dispatch", step_no):
                self.state, metrics = self._train_steps_fn(self.state, device_batch, rng)
            self.global_steps += n_steps
            self.global_samples += n_steps * self.config.train_batch_size
            self.micro_steps += n_steps * self.config.gradient_accumulation_steps
            # the step's one wait for the device: the throughput timer's sync
            with tel.span("device_wait", step_no):
                self.timers(TRAIN_BATCH_TIMER).stop()
                self.tput_timer.stop(global_step=True)
            # every step in the stack counts toward overflow accounting, not just
            # the last one (_post_step sees a scalar; the stack's total lands here)
            ov_steps = np.asarray(jax.device_get(metrics["overflow"]))
            ls_steps = np.asarray(jax.device_get(metrics["loss_scale"]))
            n_over = int(np.sum(ov_steps))
            last = jax.tree.map(lambda m: m[-1], metrics)
            if n_over:
                self.skipped_steps += n_over
                log_dist(f"{n_over}/{n_steps} steps in the fused stack overflowed; "
                         f"updates skipped, loss scale -> {float(last['loss_scale'])}")
            # per-step flags (already host-synced above) feed the overflow
            # watcher so streaks inside a fused stack trip the same guard the
            # per-dispatch path does. Drain first: earlier per-dispatch steps
            # may still sit in _pending_overflow, and the watcher must see
            # flags in step order or a stale streak replays after clean steps
            self._drain_overflows()
            first = self.global_steps - n_steps
            for i in range(n_steps):
                self._record_overflow(first + i + 1, bool(ov_steps[i]), float(ls_steps[i]))
            # drop the key entirely (not overflow=False): a synthetic clean flag
            # for the final step would reach the watcher at the next drain and
            # zero a streak the real per-step flags above just built — the
            # abort-after-K guard must see fused stacks exactly as per-dispatch
            last = {k: v for k, v in last.items() if k != "overflow"}  # counted above
            with tel.span("post_step", step_no):
                self._post_step(last)
        tel.end_step(self.global_steps, n_steps=n_steps)
        self._maybe_trace_window()
        return metrics["loss"]

    # ------------------------------------------------------------------
    # training API
    # ------------------------------------------------------------------
    def train_batch(self, batch=None, data_iter=None):
        """One full optimization step over a global batch
        (fwd+bwd+optimizer fused under jit)."""
        if batch is None:
            it = data_iter or self._training_iterator()
            if it is None:
                raise ValueError("train_batch needs a batch or a data iterator")
            batch = next(it)
        # the autotuner must cost candidates at the FULL sequence length, not
        # the curriculum's warm-up difficulty — tune before truncating
        self._maybe_autotune(batch)
        if self.curriculum_scheduler is not None and self.curriculum_metric == "seqlen":
            seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
            batch = _truncate_seq(batch, seqlen)
        self.initialize_state(batch)
        if (getattr(self, "_retain_grads_flag", False)
                and getattr(self, "_host_opt", None) is None
                and self._zeroone_runner is None and self._onebit_cfg is None):
            return self._train_batch_retained(batch)
        leaves = jax.tree.leaves(batch)
        if (leaves and np.ndim(leaves[0]) > 0 and jax.process_count() == 1
                and np.shape(leaves[0])[0] != self.config.train_batch_size
                and not getattr(self, "_warned_batch_mismatch", False)):
            self._warned_batch_mismatch = True
            logger.warning(f"train_batch received {np.shape(leaves[0])[0]} samples but "
                           f"config.train_batch_size={self.config.train_batch_size} "
                           f"(autotuning run mode changes the batch triangle — feed "
                           f"engine.train_batch_size samples); sample accounting will drift")
        self._maybe_write_telemetry_header(batch)
        self._maybe_trace_window()
        tel = self.telemetry
        step_no = self.global_steps + 1
        tel.begin_step(step_no)
        with tel.span("train_batch", step_no, trace.UNIT):
            with tel.span("timer_sync", step_no):
                self.tput_timer.start()
            self.timers(TRAIN_BATCH_TIMER).start()
            with tel.span("batch_stage", step_no):
                device_batch = self._shard_batch(batch, with_gas_dim=True)
            rng = jax.random.fold_in(self._base_rng, self.global_steps)
            fp_cfg = self.config.flops_profiler_config
            profiling_now = fp_cfg.enabled and step_no == fp_cfg.profile_step
            if profiling_now:
                t_profile = time.time()
            with tel.span("dispatch", step_no):
                if getattr(self, "_host_opt", None) is not None:
                    _, metrics = self._offload_train_batch(device_batch, rng)
                elif self._zeroone_runner is not None:
                    # 0/1 Adam owns the whole schedule (dense/1-bit/local/sync)
                    metrics = self._zeroone_runner.step(device_batch, rng)
                elif (self._onebit_cfg is not None
                      and self.global_steps >= self._onebit_cfg["freeze_step"]):
                    # compression phase: momentum rides the 1-bit collective
                    if self._onebit_step_fn is None:
                        self._build_onebit_step_fn(device_batch)
                    self.state, self._onebit_errors, metrics = self._onebit_step_fn(
                        self.state, self._onebit_errors, device_batch, rng)
                elif getattr(self, "_param_offload_enabled", False):
                    metrics = self._param_offload_train_batch(device_batch, rng)
                else:
                    self.state, metrics = self._train_step_fn(self.state, device_batch, rng)
            self.global_steps += 1
            self.global_samples += self.config.train_batch_size
            self.micro_steps += self.config.gradient_accumulation_steps
            if profiling_now:
                jax.block_until_ready(metrics["loss"])
                step_latency = time.time() - t_profile
            # the step's one wait for the device is the throughput timer's sync:
            # the span splits "host dispatched" from "device finished" and adds
            # no sync of its own, whether or not the JSONL sink is on
            with tel.span("device_wait", step_no):
                self.timers(TRAIN_BATCH_TIMER).stop()
                self.tput_timer.stop(global_step=True)
            if profiling_now:
                # reference hooks the profiler at flops_profiler_profile_step
                # (engine.py:1721,2121); here the compiled step IS the profile.
                # Runs after the timers close so profiler-induced (re)compiles
                # don't pollute the step's recorded throughput.
                from deepspeed_tpu.profiling.flops_profiler.profiler import profile_engine_step
                profile_engine_step(self, device_batch, rng,
                                    step_latency_s=step_latency,
                                    output_file=fp_cfg.output_file)
            self._last_batch_for_stats = batch  # MoE gate observability (_post_step)
            with tel.span("post_step", step_no):
                self._post_step(metrics)
        tel.end_step(self.global_steps)
        self._maybe_trace_window()  # close the window right after its last step
        return metrics["loss"]

    def eval_batch(self, batch):
        self.initialize_state(batch)
        self._ensure_params_resident()
        device_batch = self._shard_batch(batch, with_gas_dim=False)
        return self._eval_step_fn(self.state.params, device_batch, self.state.step)

    def moe_gate_stats(self, batch):
        """Per-MoE-layer expert-load statistics from one diagnostic forward
        (train-mode gating: train capacity factor, RTS/noise live, rng keyed
        off the current step). The forward is jitted once and reused, and
        the batch goes through ``_shard_batch`` like every other engine
        dispatch — on a mesh it runs sharded, not replicated; cost is one
        compiled forward per call (``_post_step`` calls at
        ``steps_per_print`` cadence, only with a monitor backend enabled).
        Returns ``{layer: {"exp_counts": [E], "kept_counts": [E],
        "routed_counts": [E] (when the route exposes it), "capacity_slots":
        int}}`` — the gate sows these (``MOELayer``), and
        ``monitor.moe_gate_events`` turns them into drop-fraction /
        capacity-utilization / load-balance series so ``capacity_factor``
        is tuned from data instead of guessed."""
        self.initialize_state(batch)
        self._ensure_params_resident()
        device_batch = self._shard_batch(batch, with_gas_dim=False)
        if self._moe_stats_fn is None:
            def _stats(params, mb, key):
                ids = mb["input_ids"] if isinstance(mb, dict) else mb
                extra = self._module_kwargs(mb)
                cparams = _cast_floating(params, self.compute_dtype)
                drop_key, gate_key = jax.random.split(key)
                _, ivars = self.module.apply({"params": cparams}, ids,
                                             deterministic=False,
                                             rngs={"dropout": drop_key, "gating": gate_key},
                                             mutable=["intermediates"], **extra)
                return ivars["intermediates"]

            self._moe_stats_fn = jax.jit(_stats)
        inter = jax.device_get(self._moe_stats_fn(
            self.state.params, device_batch,
            jax.random.fold_in(self._base_rng, self.global_steps)))

        stats = {}

        def walk(node, path):
            if not isinstance(node, dict):
                return
            if "exp_counts" in node and "kept_counts" in node:
                layer = "/".join(p for p in path if p) or "moe"
                entry = {
                    "exp_counts": np.asarray(node["exp_counts"][0]),
                    "kept_counts": np.asarray(node["kept_counts"][0]),
                    "capacity_slots": int(node["capacity_slots"][0]),
                }
                if "routed_counts" in node:
                    entry["routed_counts"] = np.asarray(node["routed_counts"][0])
                stats[layer] = entry
                return
            for k, v in node.items():
                walk(v, path + [k])

        walk(inter, [])
        return stats

    def retain_grads(self, flag: bool = True):
        """Keep each optimization step's averaged full-precision gradients
        alive for ``utils.tensor_fragment.safe_get_full_grad`` (reference
        keeps grads naturally as ``param.grad``; the fused XLA step consumes
        them inside one program, so retention re-routes ``train_batch``
        through the forward/backward/step shims)."""
        self._retain_grads_flag = bool(flag)
        if not flag:
            self._retained_grads = None

    def _train_batch_retained(self, batch):
        """train_batch via the shim path so gradients survive the step."""
        gas = self.config.gradient_accumulation_steps
        sized = [np.shape(l)[0] for l in jax.tree.leaves(batch) if np.ndim(l) > 0]
        if not sized:
            raise ValueError("retain_grads train_batch needs at least one batched leaf")
        b = sized[0]
        assert b % gas == 0, f"global batch {b} not divisible by GAS {gas}"
        mb_size = b // gas

        def slice_leaf(x, i):
            x = np.asarray(x)
            # scalar / unbatched leaves (e.g. per-batch weights) pass through,
            # matching the fused path's _shard_batch tolerance
            if x.ndim == 0 or x.shape[0] != b:
                return x
            return x[i * mb_size:(i + 1) * mb_size]

        losses = []
        for i in range(gas):
            mb = jax.tree.map(lambda x: slice_leaf(x, i), batch)
            losses.append(self.forward(mb))
            self.backward()
        self.step()
        return jnp.mean(jnp.stack(losses))

    # -- torch-style shims (reference engine.py:1709/1850/2051) ----------
    def forward(self, batch):
        """Compute the (scaled-down-by-GAS) loss for one microbatch and
        stash it for ``backward``. Returns the loss array."""
        self.initialize_state(batch)
        if getattr(self, "_host_opt", None) is not None:
            raise NotImplementedError("offload_optimizer requires the fused train_batch() path; "
                                      "the forward/backward/step shims keep state on device")
        if getattr(self, "_param_offload_enabled", False):
            raise NotImplementedError("offload_param requires the fused train_batch() path; "
                                      "the forward/backward/step shims donate device-resident "
                                      "state that offload keeps in host memory")
        self._pending_batch = self._shard_batch(batch, with_gas_dim=False)
        key = jax.random.fold_in(self._base_rng, self.micro_steps)
        scale = self.state.loss_scale.loss_scale if self._fp16_mode else jnp.float32(1.0)
        loss, grads = self._micro_grad_fn(self.state.params, self._pending_batch, key, scale)
        self._pending_grads = grads
        return loss

    def backward(self, loss=None, allreduce_gradients=True):
        """Accumulate the pending microbatch's gradients (reference
        ``engine.py:1850``; reduction itself is deferred to the GAS
        boundary inside ``step``)."""
        if getattr(self, "_pending_grads", None) is None:
            raise RuntimeError("backward() must follow forward()")
        if self._grad_acc is None:
            self._grad_acc = self._pending_grads
        else:
            self._grad_acc = jax.tree.map(jnp.add, self._grad_acc, self._pending_grads)
        self._pending_grads = None
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self):
        """Reference ``engine.py:1936``."""
        return (self.micro_steps % self.config.gradient_accumulation_steps) == 0

    def step(self):
        """Apply the optimizer update at the GAS boundary (reference
        ``engine.py:2051``); no-op otherwise."""
        if not self.is_gradient_accumulation_boundary():
            return
        n_micro = self.config.gradient_accumulation_steps
        if getattr(self, "_retain_grads_flag", False):
            # averaged, unscaled grads for utils.tensor_fragment debug access.
            # The apply call below DONATES _grad_acc: the eager divisions
            # must finish materializing before XLA reuses those buffers as
            # scratch, or the retained copies read garbage
            scale = float(self.state.loss_scale.loss_scale) if self._fp16_mode else 1.0
            self._retained_grads = jax.block_until_ready(jax.tree.map(
                lambda g: g / (n_micro * scale), self._grad_acc))
        self.state, metrics = self._apply_grads_fn(self.state, self._grad_acc, n_micro)
        self._grad_acc = None
        self.global_steps += 1
        self.global_samples += self.config.train_batch_size
        self._post_step(metrics)

    def _maybe_trace_window(self, n_steps: int = 1):
        """Open/close the XLA trace capture window (trace_profiler config —
        the reference wraps its loop in torch.profiler externally; here the
        engine owns the window so one config flag captures a device trace).
        Called before AND after each train_batch/train_batches dispatch so
        the window closes as soon as its last step has run, not on the next
        call (which may never come). ``n_steps``: how many steps the next
        dispatch runs — a fused stack whose RANGE intersects the window
        opens it (window granularity = dispatch granularity)."""
        tc = getattr(self.config, "trace_profiler_config", None)
        if tc is None or not tc.enabled:
            return
        step = self.global_steps + 1
        if (not getattr(self, "_trace_active", False)
                and step < tc.start_step + tc.num_steps
                and step + n_steps > tc.start_step):
            import jax.profiler
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = tc.host_tracer_level
            opts.python_tracer_level = 1 if tc.python_tracer else 0
            jax.profiler.start_trace(tc.output_dir, profiler_options=opts)
            self._trace_active = True
            self.telemetry.emit("xla_trace", phase="start", step=step,
                                output_dir=tc.output_dir)
            log_dist(f"XLA trace capture started at step {step} -> {tc.output_dir}")
        elif getattr(self, "_trace_active", False) and step >= tc.start_step + tc.num_steps:
            import jax.profiler
            # drain in-flight device work so the closing trace has the ops
            if self.state is not None:
                jax.block_until_ready(self.state.params)
            jax.profiler.stop_trace()
            self._trace_active = False
            self.telemetry.emit("xla_trace", phase="stop", step=step - 1,
                                output_dir=tc.output_dir)
            log_dist(f"XLA trace capture stopped after step {step - 1}")

    def _post_step(self, metrics):
        # metric semantics note: during a 1-bit/0-1 Adam
        # compression phase there IS no globally-reduced gradient, so
        # "grad_norm" carries the compressed-update norm instead (the step
        # functions also emit it under the explicit key) — reference 1-bit
        # Adam simply stops reporting; we keep the series with changed meaning
        #
        # NO eager float()/bool() on per-step metrics here: a host conversion
        # blocks on the step's completion, serializing dispatch (the next
        # step cannot be enqueued behind a host sync). Device arrays are
        # stashed and resolved lazily — in accessors, at steps_per_print
        # boundaries, or when the pending-overflow window fills.
        # liveness signal for DSElasticAgent supervision: a cheap utime when
        # DS_ELASTIC_HEARTBEAT_FILE is set, a no-op otherwise — no device
        # sync involved, and cadenced (resilience.heartbeat_interval) so the
        # steady state costs one time-read per step, one utime per interval
        from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat
        touch_heartbeat(min_interval=self.config.resilience_config.heartbeat_interval,
                        payload={"global_step": self.global_steps,
                                 "last_span": self.telemetry.last_span,
                                 # topology stamp: the elastic agent reads
                                 # reshard-vs-plain straight off the pulse.
                                 # SAME shape as the metadata.json stamp
                                 # (full axis dict) so the two compare with
                                 # plain equality
                                 "world_size": int(self.mesh.devices.size),
                                 "mesh_axes": {str(a): int(s)
                                               for a, s in self.mesh.shape.items()}})
        if self.progressive_layer_drop is not None:
            # host mirror of the in-graph schedule (reference update_state)
            self.progressive_layer_drop.update_state(self.global_steps)
        if "compressed_update_norm" in metrics:
            self._last_compressed_update_norm = metrics["compressed_update_norm"]
        if "grad_norm" in metrics:
            self._last_grad_norm = metrics["grad_norm"]
        if "step_counts" in metrics:
            self._record_step_counts(metrics["step_counts"])
        ov = metrics.get("overflow")
        if ov is not None:
            self._pending_overflow.append((self.global_steps, ov, metrics.get("loss_scale")))
        if (len(self._pending_overflow) >= 16
                or self.global_steps % self.config.steps_per_print == 0):
            self._drain_overflows()
        if (self.telemetry.has_consumers
                and self.global_steps % self.config.steps_per_print == 0):
            events = [(f"Train/loss", float(metrics.get("loss", 0.0)), self.global_samples),
                      (f"Train/lr", self.get_lr()[0], self.global_samples)]
            if self._resilience_events:
                events, self._resilience_events = events + self._resilience_events, []
            if self._fp16_mode:
                events.append((f"Train/loss_scale", float(metrics["loss_scale"]), self.global_samples))
            if getattr(self, "_last_step_counts", None) is not None:
                # the step's own device-side counts, summed over its layers
                events += [(f"MoE/step_{name}", float(self._last_step_counts[:, at].sum()),
                            self.global_samples)
                           for at, name in enumerate(self._step_count_names())]
            batch = getattr(self, "_last_batch_for_stats", None)
            mcfg = getattr(self.module, "config", None)
            if batch is not None and mcfg is not None and getattr(mcfg, "moe_num_experts", 0) > 0:
                from deepspeed_tpu.monitor.monitor import moe_gate_events
                try:
                    events += moe_gate_events(self.moe_gate_stats(batch), self.global_samples)
                except Exception as e:  # observability must never kill a step
                    logger.warning(f"moe gate stats collection failed: {e}")
            # the event bus: MonitorMaster is a subscriber, the JSONL log
            # (telemetry enabled) gets the same batch durably
            with self.telemetry.span("monitor_flush"):
                self.telemetry.publish_events(events, step=self.global_samples)
        if self.config.wall_clock_breakdown and self.global_steps % self.config.steps_per_print == 0:
            self.timers.log([TRAIN_BATCH_TIMER])
        # deterministic process-death injection (resilience/faults.py): armed
        # only via DS_FAULT_SPEC, otherwise one cached dict lookup
        fault_point("step", step=self.global_steps)
        # a SIGTERM/SIGINT that landed mid-step is honored HERE, at the step
        # boundary, with a normal verified checkpoint — preemption costs one
        # step, not the run
        self._maybe_preempt_checkpoint()

    # ------------------------------------------------------------------
    # accessors (parity with engine property surface, engine.py:474-855)
    # ------------------------------------------------------------------
    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def train_batch_size(self):
        return self.config.train_batch_size

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.config.zero_optimization_stage

    def get_lr(self):
        if self.lr_scheduler is not None:
            return [float(self.lr_scheduler(self.global_steps))]
        params = self.config.optimizer_params or {}
        return [params.get("lr", 1e-3)]

    def get_global_grad_norm(self):
        gn = getattr(self, "_last_grad_norm", None)
        return None if gn is None else float(gn)

    @property
    def cur_scale(self):
        """Current loss scale (reference ``engine.py`` exposes
        ``optimizer.cur_scale``; 1.0 outside fp16 mode). Before the first
        batch the configured initial scale reports, as in the reference."""
        if self.state is not None and self.state.loss_scale is not None:
            return float(self.state.loss_scale.loss_scale)
        return float(self._ls_state0.loss_scale)

    def get_loss_scale(self):
        return self.cur_scale

    # reference accessor surface (engine.py:474-855) — thin views over the
    # typed config / mesh so user scripts written against the reference keep
    # working
    @property
    def global_rank(self) -> int:
        return dist.get_rank()

    @property
    def world_size(self) -> int:
        return dist.get_world_size()

    @property
    def dp_world_size(self) -> int:
        # expert x data x fsdp — the batch-sharding world the config's
        # batch triangle resolves against (topology.data_parallel_size)
        return self.topology.data_parallel_size

    @property
    def mp_world_size(self) -> int:
        return self.topology.tensor_parallel_size

    def dynamic_loss_scale(self) -> bool:
        # loss_scale == 0 selects dynamic scaling (reference convention)
        return bool(self.config.fp16_enabled and self.config.fp16_config.loss_scale == 0)

    def gradient_clipping(self) -> float:
        return self.config.gradient_clipping

    def steps_per_print(self) -> int:
        return self.config.steps_per_print

    def bfloat16_enabled(self) -> bool:
        return bool(self.config.bfloat16_enabled)

    def fp16_enabled(self) -> bool:
        # a METHOD as in the reference (engine.py:779); the internal bool
        # rides self._fp16_mode to keep this name callable
        return bool(self.config.fp16_enabled)

    def wall_clock_breakdown(self) -> bool:
        return bool(self.config.wall_clock_breakdown)

    def zero_offload_optimizer(self):
        return self.config.zero_config.offload_optimizer

    @property
    def communication_data_type(self):
        """Resolved wire dtype (reference ``engine.py:797``): the configured
        dtype if set, else the enabled compute precision (fp16 -> float16,
        bf16 -> bfloat16, else float32) — a jnp dtype, comparable against
        tensor dtypes, never the raw config string."""
        resolved = _comm_dtype(self.config)
        if resolved is not None:
            return resolved
        if getattr(self.config, "communication_data_type", None) is not None:
            return jnp.float32  # explicitly configured fp32
        if self.fp16_enabled():
            return jnp.float16
        if self.bfloat16_enabled():
            return jnp.bfloat16
        return jnp.float32

    def sparse_gradients_enabled(self) -> bool:
        return bool(self.config.sparse_gradients_enabled)

    def _drain_overflows(self):
        """Resolve deferred per-step overflow flags (host sync happens HERE,
        off the dispatch critical path). Each drained flag also feeds the
        overflow watcher: loss-scale-cut / skip-streak monitor events, and
        the abort-after-K guard (``resilience.max_consecutive_overflows``
        raises ``OverflowAbort`` — a poisoned run fails fast)."""
        pending, self._pending_overflow = self._pending_overflow, []
        for step, ov, ls in pending:
            ls_f = float(ls) if ls is not None else None
            if bool(ov):
                self._skipped_steps += 1
                ls_txt = f", loss scale -> {ls_f}" if ls_f is not None else ""
                log_dist(f"step {step} overflow: skipped update{ls_txt}")
            self._record_overflow(step, bool(ov), ls_f)

    def _record_overflow(self, step, overflow: bool, loss_scale):
        """One host-resolved per-step flag → watcher events (buffered for the
        next monitor write) + the fail-fast guard."""
        events = self._overflow_watcher.record(step, overflow, loss_scale)
        if events and self.telemetry.has_consumers:
            # monitor x-axis is samples, like the Train/* series; buffered
            # for the next _post_step bus publish so a telemetry-only run
            # (no monitor backend) still lands Resilience/* in the JSONL
            self._resilience_events.extend(
                (tag, value, ev_step * self.config.train_batch_size)
                for tag, value, ev_step in events)

    @property
    def skipped_steps(self) -> int:
        self._drain_overflows()
        return self._skipped_steps

    @skipped_steps.setter
    def skipped_steps(self, value: int):
        # assigning the counter (init, checkpoint load) abandons any
        # not-yet-drained flags from the previous timeline — they must not
        # leak into the restored count
        self._pending_overflow = []
        self._skipped_steps = int(value)

    @property
    def module_params(self):
        return self.state.params if self.state is not None else None

    # ------------------------------------------------------------------
    # resilience: preemption-to-checkpoint + verified resume
    # ------------------------------------------------------------------
    def enable_preemption_checkpoint(self, save_dir, signals=None, exit_after_save=None,
                                     exit_code=None):
        """Arm preemption-safe checkpointing: SIGTERM/SIGINT set a flag (the
        handler does nothing else — async-signal-safe), and the next step
        boundary saves a verified checkpoint to ``save_dir``, then exits
        ``exit_code`` (143 by default, so a supervisor relaunches instead of
        reading the exit as job-finished). Config path: the
        ``resilience.preempt_save_dir`` key arms this at engine init."""
        from deepspeed_tpu.runtime.resilience.signals import PreemptionGuard
        rcfg = self.config.resilience_config
        self._preempt_save_dir = save_dir
        if exit_after_save is not None:
            self._preempt_exit = bool(exit_after_save)
        if exit_code is not None:
            self._preempt_exit_code = int(exit_code)
        if self._preemption is not None:
            self._preemption.uninstall()
        self._preemption = PreemptionGuard(signals or rcfg.preempt_signals).install()
        log_dist(f"preemption checkpointing armed: {self._preemption.signal_names} -> "
                 f"checkpoint at next step boundary -> {save_dir}")
        return self._preemption

    def _maybe_preempt_checkpoint(self):
        g = self._preemption
        if g is None:
            return
        requested = g.requested
        if jax.process_count() > 1:
            # the signal rarely reaches every host inside the same step: the
            # boundary decision must be COLLECTIVE (any rank's flag → all
            # ranks save now), or ranks enter the collective save at
            # different steps and deadlock. Armed multi-host runs pay one
            # small host allgather per boundary for this.
            from jax.experimental import multihost_utils
            requested = bool(np.any(multihost_utils.process_allgather(
                np.asarray(requested))))
        if not requested:
            return
        sig = g.consume() or "peer-rank signal"
        log_dist(f"preemption signal {sig}: checkpointing at step boundary "
                 f"{self.global_steps} -> {self._preempt_save_dir}")
        self.save_checkpoint(self._preempt_save_dir)
        self.flush_checkpoints()  # durability before the exit below
        self.telemetry.publish_events([
            ("Resilience/preempt_checkpoint", float(self.global_steps), self.global_samples)],
            step=self.global_samples)
        self.telemetry.emit("preempt_checkpoint", signal=sig, step=self.global_steps,
                            save_dir=self._preempt_save_dir)
        if self._preempt_exit:
            log_dist(f"preemption checkpoint durable; exiting {self._preempt_exit_code}")
            raise SystemExit(self._preempt_exit_code)

    def _resume_preamble(self, load_dir):
        """The shared pre-restore sequence of :meth:`resume` and
        :meth:`resume_elastic`: commit any in-flight async save (the sweep
        below would destroy its live staging mid-write), run the
        crash-window staging sweep rank-0-only (a tag overwrite killed
        between its displace and publish renames left the intact copy
        under ``.tmp.<tag>.old.*`` — restore it before listing), barrier,
        and return the published tags newest-first. One copy of this
        ordering: both resume paths MUST observe identical sweep/list
        semantics or their tag resolution drifts."""
        from deepspeed_tpu.runtime.resilience.manifest import (list_checkpoint_tags,
                                                               sweep_stale_staging)
        self.flush_checkpoints()
        if dist.get_rank() == 0:
            sweep_stale_staging(load_dir)
        dist.barrier()
        return list_checkpoint_tags(load_dir)

    def resume(self, load_dir=None, tag=None):
        """Preemption-safe auto-resume: restore from the newest intact
        checkpoint under ``load_dir`` (default: the armed preemption dir).
        Restores the full timeline — params/optimizer/``state.step`` (which
        the LR schedule reads), dynamic loss scale, and the step counters
        the per-step RNG folds in — so the continued run is bit-exact with
        the uninterrupted one (tests/unit/resilience/test_resume_parity).

        Tolerates a crash between checkpoint publish and the ``latest``
        marker: with no/stale marker it resolves the newest intact tag
        directly. Returns ``(tag, client_state)`` — ``(None, {})`` means no
        checkpoint exists yet (fresh start)."""
        load_dir = load_dir or self._preempt_save_dir
        assert load_dir, "resume() needs a load_dir (or an armed resilience.preempt_save_dir)"
        tags = self._resume_preamble(load_dir)
        if not tags:
            log_dist(f"resume: no checkpoints under {load_dir}; fresh start")
            return None, {}
        if tag is None and not os.path.exists(os.path.join(load_dir, "latest")):
            logger.warning(f"resume: {load_dir} has tags but no 'latest' marker (crash "
                           f"between publish and marker?); using newest intact tag")
            tag = tags[0]
        path, client = self.load_checkpoint(load_dir, tag=tag)
        if path is None:
            return None, {}
        loaded = getattr(self, "_loaded_checkpoint_tag", tag)
        log_dist(f"resumed from checkpoint {loaded} at step {self.global_steps} "
                 f"(samples {self.global_samples}, loss scale {float(self.cur_scale)})")
        return loaded, client

    def resume_elastic(self, load_dir=None, tag=None):
        """World-size-elastic resume (graft-elastic): restore the newest
        intact checkpoint onto THIS engine's mesh, whatever topology wrote
        it. Same topology delegates to the bit-exact plain path; a changed
        topology is planned on the host first (feasibility + gather bytes,
        ``runtime/elastic/planner.py``) and refused loudly on axes the plan
        cannot satisfy — before any deserialization. Every restored leaf is
        re-hashed against its save-time digest (the digest covers the
        logical global array), so a completed reshard is *proven* bit-exact.
        Returns a :class:`~deepspeed_tpu.runtime.elastic.resume.ReshardReport`
        (iterable as ``(tag, client_state)`` like :meth:`resume`)."""
        from deepspeed_tpu.runtime.elastic.resume import resume_elastic
        return resume_elastic(self, load_dir, tag=tag)

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:2906 save / 2601 load)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True):
        self._ensure_params_resident()
        from deepspeed_tpu.runtime.checkpoint_engine.orbax_engine import OrbaxCheckpointEngine
        assert self.state is not None, "nothing to checkpoint: state not initialized"
        tag = tag or f"global_step{self.global_steps}"
        use_async = bool(self.config.nebula_config.enabled)
        # one pending async save at a time: entering a new save commits the
        # previous one (its 'latest' marker lands then)
        self.flush_checkpoints()
        engine = OrbaxCheckpointEngine(save_dir, use_async=use_async)
        meta = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            # topology stamp (graft-elastic): lets a supervisor decide
            # reshard-vs-plain-resume from metadata alone, without ever
            # opening the state (elastic/agent.decide_resume,
            # list_checkpoint_tags(with_meta=True))
            "world_size": int(self.mesh.devices.size),
            "mesh_axes": {str(a): int(s) for a, s in self.mesh.shape.items()},
            "client_state": client_state or {},
        }
        if self.curriculum_scheduler is not None:
            meta["curriculum_state"] = self.curriculum_scheduler.get_state()
        # per-leaf layout manifest (logical shape/dtype/PartitionSpec vs
        # named mesh axes): what makes the published tag world-size-
        # independent by construction — any target mesh plans its reshard
        # against this, and the per-leaf digests prove the reshard bit-exact
        from deepspeed_tpu.runtime.elastic.layout import engine_layout
        layout = engine_layout(self)
        # stage-then-publish: state AND the extra per-rank files below land
        # in the staging dir and become visible in ONE atomic rename
        # (finalize) — a killed writer never leaves a partial tag
        _ckpt_t0 = time.perf_counter()
        with self.telemetry.span("ckpt_stage"):
            engine.save(self.state, tag, metadata=meta, defer_finalize=True,
                        layout=layout)
        stage = engine.staging_dir(tag)
        if self._zeroone_runner is not None:
            # pending local updates (u) + error feedback are optimizer state.
            # state_dict() runs a process_allgather on multi-host meshes, so
            # EVERY rank must call it; only the write is rank-0
            zo_state = self._zeroone_runner.state_dict()
            if dist.get_rank() == 0:
                np.save(os.path.join(stage, "zeroone_state.npy"),
                        zo_state, allow_pickle=True)
        if getattr(self, "_host_opt", None) is not None:
            # offloaded optimizer state (host masters + moments bookkeeping).
            # Shard mode (multi-host): every process owns a disjoint master
            # partition, so every process writes its own file — the
            # reference's per-rank optimizer checkpoint model.
            fname = (f"host_optimizer_proc{dist.get_rank()}.npy"
                     if getattr(self, "_host_shard_mode", False)
                     else "host_optimizer.npy")
            if getattr(self, "_host_shard_mode", False) or dist.get_rank() == 0:
                np.save(os.path.join(stage, fname),
                        {"opt": self._host_opt.state_dict(),
                         "masters": self._host_masters}, allow_pickle=True)
        if use_async:
            # Nebula-style deferral: training continues while orbax
            # finalizes in the background; 'latest' (the durability marker)
            # is written by flush_checkpoints() / the next save. A process
            # exit with a pending save would leave a torn
            # *.orbax-checkpoint-tmp — commit it from atexit.
            self._pending_ckpt = (engine, save_dir, tag, save_latest)
            if not getattr(self, "_flush_atexit", False):
                import threading
                import weakref
                ref = weakref.ref(self)

                def _flush_on_exit():
                    eng = ref()
                    if eng is not None:
                        try:
                            eng.flush_checkpoints()
                        except Exception as e:  # noqa: BLE001 — exit path
                            logger.warning(f"atexit checkpoint flush failed: {e}")

                # plain atexit runs AFTER concurrent.futures' executor
                # shutdown, which orbax's background commit still needs —
                # threading's exit hooks run before that teardown
                register = getattr(threading, "_register_atexit", None)
                if register is None:  # very old Python: best-effort
                    import atexit
                    register = atexit.register
                register(_flush_on_exit)
                self._flush_atexit = True
            self.telemetry.emit("checkpoint", tag=tag, step=self.global_steps,
                                dur_s=time.perf_counter() - _ckpt_t0, deferred=True)
            return True
        with self.telemetry.span("ckpt_publish"):
            dist.barrier()  # all ranks' staged writes land before the publish
            engine.finalize(tag)  # manifest + fsync + atomic rename (rank-0 rename)
            if save_latest and dist.get_rank() == 0:
                from deepspeed_tpu.runtime.resilience.manifest import write_atomic_text
                write_atomic_text(os.path.join(save_dir, "latest"), tag)
            dist.barrier()
        self.telemetry.emit("checkpoint", tag=tag, step=self.global_steps,
                            dur_s=time.perf_counter() - _ckpt_t0, deferred=False)
        return True

    def flush_checkpoints(self):
        """Commit any pending async checkpoint (reference Nebula's persist
        boundary): blocks until the write is durable and atomically
        published, then writes its ``latest`` marker."""
        pending = getattr(self, "_pending_ckpt", None)
        if pending is None:
            return
        engine, save_dir, tag, save_latest = pending
        engine.commit(tag)  # wait for staged writes (all ranks), then finalize
        if save_latest and dist.get_rank() == 0:
            from deepspeed_tpu.runtime.resilience.manifest import write_atomic_text
            write_atomic_text(os.path.join(save_dir, "latest"), tag)
        dist.barrier()
        self._pending_ckpt = None

    def save_16bit_model(self, save_dir, output_file=None):
        self._ensure_params_resident()
        """Consolidated bf16 deployment weights from the LIVE params
        (reference ``engine.py:3376`` ``save_16bit_model`` →
        pytorch_model.bin; here an npz any flax/numpy user can read)."""
        assert self.state is not None, "nothing to save: state not initialized"
        from deepspeed_tpu.checkpoint.zero_to_fp32 import WEIGHTS_NAME, _flatten, save_npz
        cast = _cast_floating(self.state.params, jnp.bfloat16)
        if jax.process_count() > 1:
            # shards span processes: consolidate before fetching
            from jax.experimental import multihost_utils
            params = multihost_utils.process_allgather(cast)
        else:
            params = jax.device_get(cast)
        os.makedirs(save_dir, exist_ok=True)
        out = os.path.join(save_dir, output_file or WEIGHTS_NAME)
        if dist.get_rank() == 0:
            save_npz(out, _flatten(params))
        dist.barrier()
        log_dist(f"saved 16-bit model weights -> {out}")
        return out

    def load_universal(self, universal_dir):
        """Resume from a universal (HP-fragment) checkpoint, tolerating a
        changed param tree (reference ``--load-universal`` path,
        ``universal_checkpoint.py:12``)."""
        assert self.state is not None, ("initialize_state must run before load_universal "
                                        "so the target tree and shardings are known")
        from deepspeed_tpu.checkpoint.universal_checkpoint import (load_universal_into_state,
                                                                   universal_metadata)
        abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        self.state = load_universal_into_state(universal_dir, abstract, self.state_shardings)
        meta = universal_metadata(universal_dir)
        self.global_steps = meta.get("global_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        self.micro_steps = meta.get("micro_steps", 0)
        self.skipped_steps = meta.get("skipped_steps", 0)
        return meta.get("client_state", {})

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        from deepspeed_tpu.runtime.checkpoint_engine.orbax_engine import OrbaxCheckpointEngine
        from deepspeed_tpu.runtime.resilience.manifest import (CheckpointCorruptError,
                                                               list_checkpoint_tags)
        self.flush_checkpoints()  # an async save must be durable before any load
        explicit_tag = tag is not None
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.exists(latest):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        engine = OrbaxCheckpointEngine(load_dir)
        assert self.state is not None, ("initialize_state(example_batch) (or one train_batch) must run "
                                        "before load_checkpoint so shardings are known")
        # verified load with corruption fallback: the requested tag first,
        # then intact tags STRICTLY OLDER than it, newest-first — a
        # truncated or bit-flipped checkpoint costs the steps since the
        # previous intact one, never a crash and never silently-loaded
        # garbage. Never fall FORWARD: an explicit older-tag request (e.g.
        # rolling back past a divergence) must not resolve to the newer
        # state the caller is escaping.
        rcfg = self.config.resilience_config
        candidates = [tag]
        if rcfg.fallback_on_corruption:
            all_tags = list_checkpoint_tags(load_dir)
            if tag in all_tags:
                older = all_tags[all_tags.index(tag) + 1:]
            elif not explicit_tag:
                # marker-resolved tag so torn it isn't even listable: every
                # listed tag predates the marker's save — all are older
                older = all_tags
            else:
                # an EXPLICIT tag of unknown position: any fallback risks
                # falling forward — refuse and fail loudly below instead
                older = []
            candidates += [t for t in older if t != tag]
        restored = meta = None
        loaded_tag = None
        last_err = None
        for cand in candidates:
            try:
                restored, meta = engine.load(self.state, self.state_shardings, cand,
                                             load_optimizer_states=load_optimizer_states,
                                             load_module_only=load_module_only,
                                             verify=rcfg.verify_checkpoint)
                loaded_tag = cand
                break
            except CheckpointCorruptError as e:
                last_err = e
                logger.error(f"checkpoint {cand} at {load_dir} is corrupt: {e}")
                if self.telemetry.has_consumers:
                    self.telemetry.publish_events(
                        [("Resilience/checkpoint_corrupt", 1.0, self.global_samples)])
                if not rcfg.fallback_on_corruption:
                    raise
        if loaded_tag is None:
            raise CheckpointCorruptError(
                f"no intact checkpoint under {load_dir} (tried {candidates}); "
                f"last error: {last_err}")
        if loaded_tag != tag:
            logger.error(f"fell back from corrupt checkpoint {tag} to newest intact "
                         f"tag {loaded_tag} — training resumes from the older state")
            if self.telemetry.has_consumers:
                self.telemetry.publish_events(
                    [("Resilience/checkpoint_fallback", 1.0, self.global_samples)])
        tag = loaded_tag
        self._loaded_checkpoint_tag = loaded_tag
        self.state = restored
        if self._zeroone_runner is not None and load_optimizer_states:
            zo_path = os.path.join(load_dir, tag, "zeroone_state.npy")
            if os.path.exists(zo_path):
                self._zeroone_runner.load_state_dict(
                    np.load(zo_path, allow_pickle=True).item())
        if getattr(self, "_host_opt", None) is not None:
            shard_mode = getattr(self, "_host_shard_mode", False)
            fname = (f"host_optimizer_proc{dist.get_rank()}.npy" if shard_mode
                     else "host_optimizer.npy")
            host_path = os.path.join(load_dir, tag, fname)
            blob = (np.load(host_path, allow_pickle=True).item()
                    if os.path.exists(host_path) else None)
            if blob is not None:
                loaded = [np.ascontiguousarray(m, np.float32) for m in blob["masters"]]
                # same process COUNT does not imply the same shard layout
                # (mesh reshape, devices-per-proc change): validate against
                # this topology's partition before trusting per-rank files
                expect = self._build_host_masters()
                if (len(loaded) != len(expect)
                        or any(a.shape != b.shape for a, b in zip(loaded, expect))):
                    logger.warning(
                        f"host_optimizer state at {host_path} was saved under a "
                        f"different shard partition ({len(loaded)} masters vs "
                        f"{len(expect)} expected); rebuilding masters from "
                        f"restored params, optimizer moments reset")
                    blob = None
                else:
                    self._host_opt.load_state_dict(blob["opt"])
                    self._host_masters = loaded
            if blob is None:
                # no state for this process (saved without offload, or an
                # incompatible topology): rebuild masters from the restored
                # params so the next step doesn't clobber them with
                # init-time values
                logger.warning(f"no usable host_optimizer state at {host_path}; "
                               f"rebuilding fp32 masters from restored params, "
                               f"optimizer moments reset")
                self._host_masters = self._build_host_masters()
                self._host_opt.reset_state()
        self.global_steps = meta.get("global_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        self.micro_steps = meta.get("micro_steps", 0)
        self.skipped_steps = meta.get("skipped_steps", 0)
        if self.curriculum_scheduler is not None and "curriculum_state" in meta:
            self.curriculum_scheduler.set_state(meta["curriculum_state"])
        return load_dir, meta.get("client_state", {})
