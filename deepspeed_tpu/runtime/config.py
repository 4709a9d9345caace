"""Top-level config: one JSON (path or dict) → typed sub-configs.

Parity with reference ``deepspeed/runtime/config.py`` (``DeepSpeedConfig``):
the same keys, the same batch-size triangle resolution
(train_batch = micro_batch × gradient_accumulation × dp_world), with a
TPU-native ``mesh`` block replacing the implicit world-size/mpu plumbing.
"""

import json
import os
from typing import Literal, Optional

from pydantic import Field

from deepspeed_tpu.comm.config import DeepSpeedCommsConfig
from deepspeed_tpu.monitor.config import get_monitor_config
from deepspeed_tpu.profiling.config import get_flops_profiler_config, get_trace_profiler_config
from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config_utils import (DeepSpeedConfigModel, dict_raise_error_on_duplicate_keys,
                                                get_scalar_param)
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


class FP16Config(DeepSpeedConfigModel):
    """Reference ``runtime/fp16``/config keys (``runtime/config.py`` fp16 block)."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = Field(0.0, ge=0.0)  # 0 => dynamic
    initial_scale_power: int = Field(16, ge=0)
    loss_scale_window: int = Field(1000, gt=0)
    hysteresis: int = Field(2, ge=0)
    consecutive_hysteresis: bool = False
    min_loss_scale: float = Field(1.0, ge=0.0)
    fp16_master_weights_and_grads: bool = False


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False


class HybridEngineConfig(DeepSpeedConfigModel):
    """RLHF train+serve engine knobs (reference ``runtime/config.py:523``).
    ``pin_parameters``/``tp_gather_partition_size`` are accepted for config
    parity; XLA owns buffer pinning and gather granularity on TPU."""
    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference ``runtime/activation_checkpointing/config.py`` keys. On TPU
    rematerialization is `jax.checkpoint` policies; partition_activations
    maps to sequence/tensor-axis sharding of saved activations."""
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


class AttentionConfig(DeepSpeedConfigModel):
    """Flash-attention work-partitioning block (TPU-native; no reference
    analog — the reference's CUDA kernels hard-code their tiling).

    Every field is optional. Set fields are applied by the engine onto
    the model config's ``attention_blocks`` spec (``dataclasses.replace`` +
    ``module.clone``, as the "program" block); unset ones take the shape's
    defaults, measured on a v5e — see ``ops/pallas/attention_geometry.py``.
    ``cache_file`` points at a tuner's winners file (none is committed;
    ``autotuning_results/attention_blocks.json`` where one exists)."""
    block_q: Optional[int] = Field(None, ge=8)
    block_k: Optional[int] = Field(None, ge=8)
    block_q_bwd: Optional[int] = Field(None, ge=8)
    block_k_bwd: Optional[int] = Field(None, ge=8)
    policy: Optional[str] = None        # "lse" | "recompute"
    cache_file: Optional[str] = None

    def geometry_fields(self) -> dict:
        return {k: v for k, v in dict(
            block_q=self.block_q, block_k=self.block_k,
            block_q_bwd=self.block_q_bwd, block_k_bwd=self.block_k_bwd,
            policy=self.policy).items() if v is not None}


class MoEConfig(DeepSpeedConfigModel):
    """MoE dispatch/combine engine block (TPU-native; no reference analog —
    the reference's einsum route is its only formulation).

    ``route``: "dense" (the GShard/Tutel ``[G,S,E,C]`` einsum route) or
    "sorted" (token-permutation dispatch/combine). ``kernel``: permutation
    implementation for the sorted route — "auto" | "xla" | "pallas". Set
    knobs are applied by the engine onto the model config's ``moe_route`` /
    ``moe_route_kernel`` (as the "program" block); unset ones leave the
    model config's own ("sorted" / "auto" unless it says otherwise)."""
    route: Optional[Literal["dense", "sorted"]] = None
    kernel: Optional[Literal["auto", "xla", "pallas"]] = None

    def model_updates(self) -> dict:
        fields = {"moe_route": self.route, "moe_route_kernel": self.kernel}
        return {f: v for f, v in fields.items() if v is not None}


#: program-block field -> model-config field it lands on (``lm_head_chunk``
#: maps onto the zoo's ``fused_head_loss_chunk``; the rest share names)
PROGRAM_MODEL_FIELDS = {
    "remat": "remat",
    "remat_every": "remat_every",
    "remat_policy": "remat_policy",
    "lm_head_chunk": "fused_head_loss_chunk",
    "fused_qkv": "attn_fused_qkv",
    "fused_attn_out": "attn_fused_out",
}


class ProgramConfig(DeepSpeedConfigModel):
    """Traced-program shape knobs ("program" config block, TPU-native; the
    reference scatters these across activation-checkpointing flags and
    hand-fused CUDA ops).

    Every field is optional: unset knobs leave the module's model config
    untouched. Set knobs are applied by the engine onto the model config
    (``dataclasses.replace`` + ``module.clone``), so one engine JSON picks
    a program variant for any zoo family declaring the field — the
    candidate dimensions graft-search (``analysis/search.py``) enumerates
    and prices statically. ``remat_policy`` takes a
    ``runtime/activation_checkpointing`` policy name or ``"none"``;
    ``lm_head_chunk`` is tokens per chunk of the fused LM-head loss
    (0 = the unfused ``[B, L, V]`` logits head)."""
    remat: Optional[bool] = None
    remat_every: Optional[int] = Field(None, ge=1)
    remat_policy: Optional[str] = None
    lm_head_chunk: Optional[int] = Field(None, ge=0)
    fused_qkv: Optional[bool] = None
    fused_attn_out: Optional[bool] = None

    def model_updates(self) -> dict:
        """Set fields as {model_config_field: value} (``remat_policy``
        "none" normalizes to None — the unset-policy full-recompute)."""
        out = {}
        for field, model_field in PROGRAM_MODEL_FIELDS.items():
            value = getattr(self, field)
            if value is None:
                continue
            if field == "remat_policy" and value == "none":
                value = None
            out[model_field] = value
        return out


class MeshConfig(DeepSpeedConfigModel):
    """TPU-native parallel-topology block (replaces mpu/world-size plumbing).

    ``fsdp`` defaults to "auto": the engine sets it from the ZeRO stage —
    stage>=1 shards over all remaining devices (or ``zero_hpz_partition_size``
    / ``mics_shard_size`` when set)."""
    pipe: int = Field(1, ge=1)
    tensor: int = Field(1, ge=1)
    sequence: int = Field(1, ge=1)
    expert: int = Field(1, ge=1)
    data: int = -1
    fsdp: int = -1


class CheckpointConfig(DeepSpeedConfigModel):
    """Reference ``runtime/config.py`` checkpoint block."""
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: dict = {}


class NebulaConfig(DeepSpeedConfigModel):
    """Reference ``nebula/config.py`` keys. Nebula is MSFT's async
    checkpoint service; here ``enabled`` routes ``save_checkpoint`` through
    the async Orbax path — the write finalizes in the background while
    training continues, and the ``latest`` durability marker lands at the
    next save / explicit ``engine.flush_checkpoints()``. The storage/
    retention knobs are accepted for config-surface parity (orbax
    tensorstore already writes shard-parallel to the checkpoint dir)."""
    enabled: bool = False
    persistent_storage_path: Optional[str] = None
    persistent_time_interval: Optional[int] = None
    num_of_version_in_retention: int = 2
    enable_nebula_load: bool = True
    load_path: Optional[str] = None


class ResilienceConfig(DeepSpeedConfigModel):
    """Fault-tolerance block (TPU-native; no single reference analog — it
    federates the reference's nebula/elasticity/loss-scaler recovery
    behaviors into one policy surface). See ``runtime/resilience/``.

    ``verify_checkpoint``: integrity gate on load — "full" (file inventory
    before restore + per-leaf checksums after), "files", or "off".
    ``fallback_on_corruption``: a corrupt tag falls back to the newest
    intact one (loud monitor event) instead of raising.
    ``max_consecutive_overflows``: abort training after K consecutive
    overflow-skipped steps (0 = disabled) — a poisoned run fails fast
    instead of silently skipping forever.
    ``heartbeat_interval``: minimum seconds between elastic-agent
    heartbeat touches from the train loop (cadenced, off the hot path).
    ``preempt_save_dir``: when set, SIGTERM/SIGINT trigger a checkpoint at
    the next step boundary (then exit ``preempt_exit_code`` if
    ``exit_after_preempt_save``) — preemption costs one step, not the run.
    """
    verify_checkpoint: str = Field("full", pattern="^(off|files|full)$")
    fallback_on_corruption: bool = True
    max_consecutive_overflows: int = Field(0, ge=0)
    heartbeat_interval: float = Field(2.0, ge=0.0)
    preempt_save_dir: Optional[str] = None
    preempt_signals: list = ["SIGTERM", "SIGINT"]
    exit_after_preempt_save: bool = True
    preempt_exit_code: int = 143


class TelemetryConfig(DeepSpeedConfigModel):
    """graft-trace runtime telemetry block (``runtime/telemetry/``) — the
    TPU-native rebuild of the reference's observability surface
    (``monitor/monitor.py`` + ``wall_clock_breakdown`` +
    ``flops_profiler``): host-side step-phase spans, a schema-versioned
    JSONL event log, and static-vs-measured drift reporting.

    ``output_path``/``job_name``: the run directory
    (``<output_path>/<job_name>/telemetry.jsonl``).
    ``flush_interval_steps``: span/drift window cadence (0 = follow
    ``steps_per_print``). ``static_price``: stamp the step program's
    static price (flops_proxy + liveness bytes) into the run header —
    one extra jaxpr-only trace at the first step. ``span_events``: write
    the raw span timeline (``tools/trace_report.py`` input) in addition
    to the per-window aggregates. Telemetry never enters the traced
    step program (rule R015 + the ``train_batch_telemetry`` scenario)
    and must stay within 2% step-time overhead (tier-1 gate)."""
    enabled: bool = False
    output_path: str = "./telemetry_logs"
    job_name: str = "DeepSpeedJobName"
    flush_interval_steps: int = Field(0, ge=0)
    static_price: bool = True
    span_events: bool = True
    max_buffered_spans: int = Field(4096, ge=1)


class DeepSpeedConfig:
    """Parses and validates the full config (reference ``DeepSpeedConfig``,
    ``runtime/config.py``)."""

    def __init__(self, config, world_size: Optional[int] = None, dp_world_size: Optional[int] = None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(f"Expected a string path to an existing deepspeed config, got {config}")
            with open(config, "r") as f:
                self._param_dict = json.load(f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        elif isinstance(config, dict):
            self._param_dict = config
        else:
            raise ValueError(f"Expected a string path or dict, got: {config} ({type(config)})")

        self._initialize_params(self._param_dict)
        self.mesh_config = MeshConfig(**self._param_dict.get(C.MESH, {}))
        self._raw_batch_triangle = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                                    self.gradient_accumulation_steps)
        # what the USER wrote, before any elastic override — re-resolving at
        # a new world size must validate/recompute against this, not against
        # a previously-applied elastic plan
        self._user_batch_triangle = self._raw_batch_triangle
        if dp_world_size is not None:
            self.resolve_batch_for_dp(dp_world_size)
        else:
            self._resolve_batch_size(world_size)
        self._do_sanity_check()

    @property
    def raw_dict(self):
        """The user's config dict as parsed (autotuning re-derives candidate
        configs from this, not from the resolved fields)."""
        return self._param_dict

    # ------------------------------------------------------------------
    def _initialize_params(self, param_dict):
        self.train_batch_size = get_scalar_param(param_dict, C.TRAIN_BATCH_SIZE, C.TRAIN_BATCH_SIZE_DEFAULT)
        self.train_micro_batch_size_per_gpu = get_scalar_param(param_dict, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                                                               C.TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)
        self.gradient_accumulation_steps = get_scalar_param(param_dict, C.GRADIENT_ACCUMULATION_STEPS,
                                                            C.GRADIENT_ACCUMULATION_STEPS_DEFAULT)
        self.steps_per_print = get_scalar_param(param_dict, C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = get_scalar_param(param_dict, C.DUMP_STATE, C.DUMP_STATE_DEFAULT)
        self.wall_clock_breakdown = get_scalar_param(param_dict, C.WALL_CLOCK_BREAKDOWN,
                                                     C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = get_scalar_param(param_dict, C.MEMORY_BREAKDOWN, C.MEMORY_BREAKDOWN_DEFAULT)
        self.seed = get_scalar_param(param_dict, C.SEED, C.SEED_DEFAULT)

        self.gradient_clipping = get_scalar_param(param_dict, C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT)
        self.prescale_gradients = get_scalar_param(param_dict, C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = get_scalar_param(param_dict, C.GRADIENT_PREDIVIDE_FACTOR,
                                                          C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.sparse_gradients_enabled = get_scalar_param(param_dict, C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT)
        self.communication_data_type = get_scalar_param(param_dict, C.COMMUNICATION_DATA_TYPE,
                                                        C.COMMUNICATION_DATA_TYPE_DEFAULT)
        self.disable_allgather = get_scalar_param(param_dict, C.DISABLE_ALLGATHER, C.DISABLE_ALLGATHER_DEFAULT)
        self.dataloader_drop_last = get_scalar_param(param_dict, C.DATALOADER_DROP_LAST,
                                                     C.DATALOADER_DROP_LAST_DEFAULT)

        # optimizer / scheduler blocks (reference config.py get_optimizer_params)
        opt = param_dict.get(C.OPTIMIZER)
        self.optimizer_name = opt[C.TYPE].lower() if opt and C.TYPE in opt else None
        self.optimizer_params = (opt.get(C.OPTIMIZER_PARAMS, {}) if opt else None)
        self.optimizer_legacy_fusion = (opt.get(C.LEGACY_FUSION, False) if opt else False)
        sched = param_dict.get(C.SCHEDULER)
        self.scheduler_name = sched[C.TYPE] if sched and C.TYPE in sched else None
        self.scheduler_params = (sched.get(C.SCHEDULER_PARAMS, {}) if sched else None)

        # precision
        fp16_dict = param_dict.get(C.FP16, {})
        self.fp16_config = FP16Config(**fp16_dict)
        bf16_dict = param_dict.get(C.BFLOAT16, param_dict.get(C.BFLOAT16_OLD, {}))
        self.bf16_config = BF16Config(**bf16_dict)
        self.fp16_enabled = self.fp16_config.enabled
        self.bfloat16_enabled = self.bf16_config.enabled
        self.fp16_auto_cast = self.fp16_config.auto_cast
        self.loss_scale = self.fp16_config.loss_scale
        self.initial_dynamic_scale = 2**self.fp16_config.initial_scale_power
        self.dynamic_loss_scale_args = dict(init_scale=2**self.fp16_config.initial_scale_power,
                                            scale_window=self.fp16_config.loss_scale_window,
                                            min_scale=self.fp16_config.min_loss_scale,
                                            delayed_shift=self.fp16_config.hysteresis,
                                            consecutive_hysteresis=self.fp16_config.consecutive_hysteresis)

        # zero
        self.zero_config = DeepSpeedZeroConfig(**param_dict.get(C.ZERO_OPTIMIZATION, {}))
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        # subsystems
        self.activation_checkpointing_config = ActivationCheckpointingConfig(
            **param_dict.get(C.ACTIVATION_CHECKPOINTING, {}))
        self.monitor_config = get_monitor_config(param_dict)
        self.flops_profiler_config = get_flops_profiler_config(param_dict)
        self.trace_profiler_config = get_trace_profiler_config(param_dict)
        self.comms_config = DeepSpeedCommsConfig(param_dict)
        self.attention_config = AttentionConfig(**param_dict.get(C.ATTENTION, {}))
        self.moe_config = MoEConfig(**param_dict.get(C.MOE, {}))
        self.program_config = ProgramConfig(**param_dict.get(C.PROGRAM, {}))
        self.checkpoint_config = CheckpointConfig(**param_dict.get(C.CHECKPOINT, {}))
        self.nebula_config = NebulaConfig(**param_dict.get(C.NEBULA, {}))
        self.resilience_config = ResilienceConfig(**param_dict.get(C.RESILIENCE, {}))
        self.telemetry_config = TelemetryConfig(**param_dict.get(C.TELEMETRY, {}))
        self.hybrid_engine_config = HybridEngineConfig(**param_dict.get("hybrid_engine", {}))
        self.autotuning_config = param_dict.get(C.AUTOTUNING, {})
        self.elasticity_config = param_dict.get(C.ELASTICITY, {})
        self.compression_config = param_dict.get(C.COMPRESSION_TRAINING, {})
        self.data_efficiency_config = param_dict.get(C.DATA_EFFICIENCY, {})
        self.curriculum_learning_legacy = param_dict.get(C.CURRICULUM_LEARNING_LEGACY, {})
        self.curriculum_enabled_legacy = bool(self.curriculum_learning_legacy.get("enabled", False))
        pld = param_dict.get(C.PROGRESSIVE_LAYER_DROP, {})
        self.pld_enabled = bool(pld.get("enabled", False))
        self.pld_params = {"theta": float(pld.get("theta", 0.5)),
                           "gamma": float(pld.get("gamma", 0.001))}
        self.quantize_training_config = param_dict.get(C.QUANTIZE_TRAINING, {})

    # ------------------------------------------------------------------
    def _resolve_batch_size(self, world_size: Optional[int]):
        """Resolve the batch triangle (reference ``runtime/config.py``
        ``_configure_train_batch_size``): any two of {train_batch_size,
        micro_batch, gas} determine the third given dp_world_size."""
        if world_size is None:
            try:
                import jax
                world_size = jax.device_count()
            except Exception:
                world_size = 1
        mesh = self.mesh_config
        denom = mesh.pipe * mesh.tensor * mesh.sequence
        if world_size % denom != 0:
            raise DeepSpeedConfigError(f"world size {world_size} not divisible by pipe*tensor*sequence={denom}")
        self.resolve_batch_for_dp(world_size // denom)

    def resolve_batch_for_dp(self, dp_world_size: int):
        """Re-run the triangle for an explicit DP world size (used when an
        explicit MeshTopology overrides the config's mesh block)."""
        self.dp_world_size = dp_world_size
        if self.elasticity_enabled():
            # elastic training overrides the batch triangle from the
            # elasticity block (reference runtime/config.py elasticity
            # handling → elasticity/elasticity.py:233 compute_elastic_config)
            self._apply_elastic_config(dp_world_size)
        train_batch, micro_batch, grad_acc = self._raw_batch_triangle

        if train_batch is not None and micro_batch is not None and grad_acc is not None:
            pass
        elif train_batch is not None and micro_batch is not None:
            grad_acc = train_batch // micro_batch
            grad_acc //= self.dp_world_size
        elif train_batch is not None and grad_acc is not None:
            micro_batch = train_batch // self.dp_world_size
            micro_batch //= grad_acc
        elif micro_batch is not None and grad_acc is not None:
            train_batch = micro_batch * grad_acc * self.dp_world_size
        elif train_batch is not None:
            grad_acc = 1
            micro_batch = train_batch // self.dp_world_size
        elif micro_batch is not None:
            train_batch = micro_batch * self.dp_world_size
            grad_acc = 1
        else:
            raise DeepSpeedConfigError("Either train_batch_size or train_micro_batch_size_per_gpu needs to be set")

        self.train_batch_size = train_batch
        self.train_micro_batch_size_per_gpu = micro_batch
        self.gradient_accumulation_steps = grad_acc
        self._batch_assertion()

    def elasticity_enabled(self) -> bool:
        return bool(self.elasticity_config.get("enabled", False))

    def _apply_elastic_config(self, dp_world_size: int):
        """Resolve the elastic batch plan for the current chip count and
        override the batch triangle (reference config.py + ds_elastic)."""
        from deepspeed_tpu.elasticity import ElasticityConfigError, compute_elastic_config
        from deepspeed_tpu.version import __version__

        explicit = [v for v in self._user_batch_triangle if v is not None]
        if explicit and not self.elasticity_config.get("ignore_non_elastic_batch_info", False):
            raise ElasticityConfigError(
                "elasticity is enabled but train_batch_size/micro_batch/gas are also set; "
                "remove them or set elasticity.ignore_non_elastic_batch_info "
                "(reference elasticity/elasticity.py same check)")
        final_batch, valid, micro = compute_elastic_config(
            {"elasticity": self.elasticity_config}, __version__,
            world_size=dp_world_size, return_microbatch=True)
        gas = final_batch // (micro * dp_world_size)
        logger.info(f"elasticity: world={dp_world_size} -> train_batch={final_batch} "
                    f"micro={micro} gas={gas} (valid chip counts: {sorted(valid)[:8]}...)")
        self._raw_batch_triangle = (final_batch, micro, gas)

    def _batch_assertion(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        assert train_batch > 0, f"Train batch size: {train_batch} has to be greater than 0"
        assert micro_batch > 0, f"Micro batch size per gpu: {micro_batch} has to be greater than 0"
        assert grad_acc > 0, f"Gradient accumulation steps: {grad_acc} has to be greater than 0"
        assert train_batch == micro_batch * grad_acc * self.dp_world_size, (
            f"Check batch related parameters. train_batch_size is not equal to micro_batch_per_gpu * "
            f"gradient_acc_step * world_size {train_batch} != {micro_batch} * {grad_acc} * {self.dp_world_size}")

    def _do_sanity_check(self):
        # batch triangle already asserted inside resolve_batch_for_dp
        if self.fp16_enabled and self.bfloat16_enabled:
            raise DeepSpeedConfigError("fp16 and bf16 modes cannot be simultaneously enabled")
        if self.optimizer_name is not None and self.optimizer_name not in C.DEEPSPEED_OPTIMIZERS:
            logger.warning(f"optimizer {self.optimizer_name} is not a recognized built-in; "
                           "it will be looked up in the client-supplied registry")

    # ------------------------------------------------------------------
    def print(self, name="DeepSpeedConfig"):
        logger.info(f"{name}:")
        for key in sorted(self.__dict__):
            if key != "_param_dict":
                logger.info(f"  {key} {getattr(self, key)}")

    @property
    def param_dict(self):
        return self._param_dict
