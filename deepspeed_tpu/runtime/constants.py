"""Config key names and defaults.

Subset of reference ``deepspeed/runtime/constants.py`` (422 LoC) that is
meaningful on TPU, plus TPU-specific mesh keys.
"""

#############################################
# Batch-size triangle (reference constants.py)
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

#############################################
# Optimizer / scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"
MAX_GRAD_NORM = "max_grad_norm"

ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ZERO_ONE_ADAM_OPTIMIZER = "zerooneadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
SGD_OPTIMIZER = "sgd"
ADAGRAD_OPTIMIZER = "adagrad"
LION_OPTIMIZER = "lion"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER,
    ONEBIT_LAMB_OPTIMIZER, SGD_OPTIMIZER, ADAGRAD_OPTIMIZER, LION_OPTIMIZER
]

#############################################
# Precision
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_LOSS_SCALE = "loss_scale"
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_HYSTERESIS = "hysteresis"
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MASTER_WEIGHTS_AND_GRADS = "fp16_master_weights_and_grads"

BFLOAT16 = "bf16"
BFLOAT16_OLD = "bfloat16"
BFLOAT16_ENABLED = "enabled"

PRECISION_MODES = ["fp16", "bf16", "fp32"]

#############################################
# Gradients
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0
PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False
GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0
SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False
COMMUNICATION_DATA_TYPE = "communication_data_type"
COMMUNICATION_DATA_TYPE_DEFAULT = None

#############################################
# Logging / timing
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10
WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False
DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False
MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

#############################################
# Misc engine knobs
#############################################
GRADIENT_ACCUMULATION_DTYPE = "gradient_accumulation_dtype"
SEED = "seed"
SEED_DEFAULT = 1234
DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False
DATALOADER_DROP_LAST = "dataloader_drop_last"
DATALOADER_DROP_LAST_DEFAULT = False
USE_DATA_BEFORE_EXPERT_PARALLEL = "use_data_before_expert_parallelism"

#############################################
# TPU mesh (TPU-native extension; reference expresses this via mpu +
# process groups)
#############################################
MESH = "mesh"
MESH_PIPE = "pipe"
MESH_TENSOR = "tensor"
MESH_SEQUENCE = "sequence"
MESH_EXPERT = "expert"
MESH_DATA = "data"
MESH_FSDP = "fsdp"

#############################################
# Sub-configs
#############################################
ZERO_OPTIMIZATION = "zero_optimization"
ACTIVATION_CHECKPOINTING = "activation_checkpointing"
# flash-attention block geometry / backward policy (TPU-native; see
# runtime/config.py AttentionConfig, ops/pallas/attention_geometry.py)
ATTENTION = "attention"
# MoE dispatch/combine route + permutation kernel (TPU-native; see
# runtime/config.py MoEConfig)
MOE = "moe"
# traced-program shape knobs — remat policy, LM-head chunking, projection
# fusion — applied onto the module's model config by the engine; the
# dimensions graft-search enumerates (TPU-native; runtime/config.py
# ProgramConfig, analysis/search.py)
PROGRAM = "program"
COMMS_LOGGER = "comms_logger"
MONITOR_TENSORBOARD = "tensorboard"
MONITOR_WANDB = "wandb"
MONITOR_CSV = "csv_monitor"
FLOPS_PROFILER = "flops_profiler"
AUTOTUNING = "autotuning"
ELASTICITY = "elasticity"
COMPRESSION_TRAINING = "compression_training"
DATA_EFFICIENCY = "data_efficiency"
CURRICULUM_LEARNING_LEGACY = "curriculum_learning"
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
QUANTIZE_TRAINING = "quantize_training"
CHECKPOINT = "checkpoint"
NEBULA = "nebula"
RESILIENCE = "resilience"
TELEMETRY = "telemetry"
DATA_TYPES = "data_types"
