"""``init_inference`` — parity with reference ``deepspeed/__init__.py:269``."""
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.utils import trace
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.version import __version__


def init_inference(model, config=None, params=None, topology=None, **kwargs):
    """Build an :class:`InferenceEngine` (reference ``init_inference``).

    ``config`` may be a dict/``DeepSpeedInferenceConfig``; legacy kwargs
    (``mp_size=``, ``dtype=``, ``replace_with_kernel_inject=`` …) are folded
    in for parity with the reference's kwarg path (``__init__.py:306``).
    """
    with trace.recorder().span("init_inference", marks=trace.TOTAL):
        return _init_inference(model, config, params, topology, kwargs)


def _init_inference(model, config, params, topology, kwargs):
    log_dist(f"DeepSpeed-TPU inference info: version={__version__}")
    cfg_dict = dict(config) if isinstance(config, dict) else {}
    if isinstance(config, DeepSpeedInferenceConfig):
        if kwargs:
            # reference raises on conflicting config + kwargs (__init__.py:318)
            raise ValueError(f"init_inference got both a DeepSpeedInferenceConfig and kwargs "
                             f"{sorted(kwargs)}; fold the kwargs into the config")
        ds_config = config
    else:
        # legacy kwarg names (reference maps mp_size → tensor_parallel.tp_size)
        if "mp_size" in kwargs:
            cfg_dict.setdefault("tensor_parallel", {})
            if isinstance(cfg_dict["tensor_parallel"], dict):
                cfg_dict["tensor_parallel"].setdefault("tp_size", kwargs.pop("mp_size"))
        cfg_dict.update(kwargs)
        ds_config = DeepSpeedInferenceConfig(**cfg_dict)
    if hasattr(model, "state_dict") and hasattr(model, "config") and params is None:
        # HF torch module handed in directly (the reference's calling
        # convention): convert arch + config + weights in one step. int8
        # means QUANTIZED WEIGHTS, never int8 compute — match the engine's
        # cast_dtype mapping (inference/engine.py)
        import jax.numpy as jnp

        from deepspeed_tpu.module_inject.from_hf import from_hf
        compute_dtype = jnp.bfloat16 if ds_config.dtype == jnp.int8 else ds_config.dtype
        # explicit checkpoint wins over the module's own weights (the
        # reference's meta-tensor convention: arch from the module, weights
        # from the checkpoint) — skip the state_dict conversion entirely
        model, params = from_hf(model, dtype=compute_dtype,
                                weights=ds_config.checkpoint is None)
    return InferenceEngine(model, ds_config, params=params, topology=topology)
