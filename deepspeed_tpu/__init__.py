"""deepspeed_tpu: a TPU-native large-model training & inference framework.

Capability parity with DeepSpeed v0.10.1 (see SURVEY.md), built on
JAX/XLA/Pallas: sharding-spec ZeRO over a device mesh instead of runtime
hooks, `jax.lax` collectives over ICI/DCN instead of NCCL, Pallas kernels
instead of CUDA.

Public surface mirrors the reference (``deepspeed/__init__.py``):
``initialize`` (:64), ``init_inference`` (:269), ``comm``, ``zero``,
``add_config_arguments`` (:246).
"""
import time as _time

_import_t0 = _time.perf_counter()  # the package's ``import`` record starts here

from deepspeed_tpu.version import __version__, __capability_parity__

from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu import comm

__git_hash__ = None
__git_branch__ = None
git_hash = None
git_branch = None
# reference parity: deepspeed.version is the version STRING (its module
# form lives at git_version_info) — this intentionally shadows attribute
# access to the version.py submodule; import it via
# `from deepspeed_tpu.version import ...` (unaffected)
version = __version__
import re as _re

_m = _re.match(r"(\d+)\.(\d+)\.(\d+)", __version__)
__version_major__, __version_minor__, __version_patch__ = (
    (int(_m.group(1)), int(_m.group(2)), int(_m.group(3))) if _m else (0, 0, 0))
HAS_TRITON = False  # reference flag (Triton kernels; TPU uses Pallas)

# typing aliases (reference runtime/engine.py DeepSpeedOptimizerCallable /
# DeepSpeedSchedulerCallable: factories receiving params / optimizer)
from typing import Any as _Any, Callable as _Callable

DeepSpeedOptimizerCallable = _Callable[..., _Any]
DeepSpeedSchedulerCallable = _Callable[..., _Any]

_LAZY = {
    "initialize": ("deepspeed_tpu.runtime.entry", "initialize"),
    "init_inference": ("deepspeed_tpu.inference.entry", "init_inference"),
    "add_config_arguments": ("deepspeed_tpu.runtime.entry", "add_config_arguments"),
    "zero": ("deepspeed_tpu.runtime.zero", None),
    "DeepSpeedEngine": ("deepspeed_tpu.runtime.engine", "DeepSpeedEngine"),
    "DeepSpeedConfig": ("deepspeed_tpu.runtime.config", "DeepSpeedConfig"),
    "DeepSpeedConfigError": ("deepspeed_tpu.runtime.config", "DeepSpeedConfigError"),
    "DeepSpeedHybridEngine": ("deepspeed_tpu.runtime.hybrid_engine", "DeepSpeedHybridEngine"),
    "PipelineEngine": ("deepspeed_tpu.runtime.pipe.engine", "PipelineEngine"),
    "PipelineModule": ("deepspeed_tpu.runtime.pipe.module", "PipelineModule"),
    "InferenceEngine": ("deepspeed_tpu.inference.engine", "InferenceEngine"),
    "DeepSpeedInferenceConfig": ("deepspeed_tpu.inference.config", "DeepSpeedInferenceConfig"),
    "DeepSpeedTransformerLayer": ("deepspeed_tpu.ops.transformer", "DeepSpeedTransformerLayer"),
    "DeepSpeedTransformerConfig": ("deepspeed_tpu.ops.transformer", "DeepSpeedTransformerConfig"),
    "checkpointing": ("deepspeed_tpu.runtime.activation_checkpointing.checkpointing", None),
    "get_accelerator": ("deepspeed_tpu.accelerator", "get_accelerator"),
    "init_distributed": ("deepspeed_tpu.comm.comm", "init_distributed"),
    "OnDevice": ("deepspeed_tpu.utils.memory", "OnDevice"),
    "module_inject": ("deepspeed_tpu.module_inject", None),
    "ops": ("deepspeed_tpu.ops", None),
    "moe": ("deepspeed_tpu.moe", None),
    "pipe": ("deepspeed_tpu.pipe", None),
    "runtime": ("deepspeed_tpu.runtime", None),
    "DeepSpeedOptimizer": ("deepspeed_tpu.runtime", "DeepSpeedOptimizer"),
    "ZeROOptimizer": ("deepspeed_tpu.runtime", "ZeROOptimizer"),
    "ADAM_OPTIMIZER": ("deepspeed_tpu.runtime.constants", "ADAM_OPTIMIZER"),
    "LAMB_OPTIMIZER": ("deepspeed_tpu.runtime.constants", "LAMB_OPTIMIZER"),
    "add_tuning_arguments": ("deepspeed_tpu.runtime.lr_schedules", "add_tuning_arguments"),
    "replace_transformer_layer": ("deepspeed_tpu.module_inject.replace_module",
                                  "replace_transformer_layer"),
    "revert_transformer_layer": ("deepspeed_tpu.module_inject.replace_module",
                                 "revert_transformer_layer"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        import sys

        mod_name, attr = _LAZY[name]
        fresh, t0 = mod_name not in sys.modules, _time.perf_counter()
        try:
            mod = importlib.import_module(mod_name)
            if fresh:    # most of the package is imported here, at first use
                _trace.imported(mod_name, t0)
            obj = mod if attr is None else getattr(mod, attr)
        except (ImportError, AttributeError) as e:
            # keep hasattr() semantics sane for not-yet-built components
            raise AttributeError(f"deepspeed_tpu.{name} is not available: {e}") from e
        globals()[name] = obj
        return obj
    raise AttributeError(f"module 'deepspeed_tpu' has no attribute {name!r}")


def __dir__():
    # PEP 562: keep dir()/tab-completion aware of the lazy exports
    return sorted(set(globals()) | set(_LAZY))


from deepspeed_tpu.utils import trace as _trace  # noqa: E402

_trace.imported(__name__, _import_t0)
