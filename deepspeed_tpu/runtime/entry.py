"""Top-level ``initialize`` — parity with reference ``deepspeed/__init__.py:64``.

``deepspeed.initialize(args, model, ...) -> (engine, optimizer, dataloader,
lr_scheduler)``: the same 4-tuple, with JAX-native contents (the model is a
flax Module, the optimizer an optax GradientTransformation, the scheduler a
``step -> lr`` callable).
"""

import argparse
from typing import Optional

from deepspeed_tpu import comm as dist
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.utils import trace
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.version import __version__


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               topology=None,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config=None,
               config_params=None,
               loss_fn=None):
    """Build the training engine (reference ``__init__.py:64-202``).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    ``config`` is a dict or JSON path; ``args.deepspeed_config`` is honored
    for parity. ``mpu`` is accepted but unused: the mesh topology subsumes
    it (pass ``topology=`` to override)."""
    with trace.recorder().span("initialize", marks=trace.TOTAL) as span:
        built = _initialize(args, model, optimizer, model_parameters, training_data,
                            lr_scheduler, topology, dist_init_required, collate_fn, config,
                            config_params, loss_fn)
        # under the engine's source, so that its sink's first window takes the span
        span.source = built[0].telemetry.source
    return built


def _initialize(args, model, optimizer, model_parameters, training_data, lr_scheduler, topology,
                dist_init_required, collate_fn, config, config_params, loss_fn):
    assert model is not None, "deepspeed.initialize requires a model"
    log_dist(f"DeepSpeed-TPU info: version={__version__}")

    if config is None:
        config = config_params
    if config is None and args is not None and getattr(args, "deepspeed_config", None) is not None:
        config = args.deepspeed_config
    if config is None:
        # reference Init(config_dict_or_path=...) semantics: an enclosing
        # zero.Init context can carry the engine config
        from deepspeed_tpu.runtime.zero.partition_parameters import get_active_init
        active = get_active_init()
        if active is not None and active.config is not None:
            config = active.config
    assert config is not None, "DeepSpeed requires --deepspeed_config or the config= argument"

    if dist_init_required is None or dist_init_required:
        dist.init_distributed(verbose=False)

    import os
    if os.environ.get("DS_BIND_CORES"):
        # launcher --bind_cores_to_rank on a numactl-less host: the child
        # pins itself (utils/numa.py; reference launch.py:227 numactl path)
        from deepspeed_tpu.utils.numa import bind_cores_for_rank
        spec = os.environ["DS_BIND_CORES"]
        bound = bind_cores_for_rank(int(os.environ.get("DS_BIND_NPROCS", "1")),
                                    int(os.environ.get("DS_BIND_RANK", "0")),
                                    None if spec == "all" else spec)
        if bound:
            log_dist(f"bound to host cores {bound[0]}-{bound[-1]} ({len(bound)} cores)")

    with trace.recorder().span("config"):
        ds_config = DeepSpeedConfig(
            config, dp_world_size=topology.data_parallel_size if topology is not None else None)
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    if ds_config.hybrid_engine_config.enabled and not isinstance(model, PipelineModule):
        # RLHF train+serve engine (reference __init__.py:151 dispatches
        # DeepSpeedHybridEngine when config.hybrid_engine.enabled)
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
        engine = DeepSpeedHybridEngine(model=model,
                                       config=ds_config,
                                       optimizer=optimizer,
                                       loss_fn=loss_fn,
                                       lr_scheduler=lr_scheduler,
                                       topology=topology,
                                       model_parameters=model_parameters,
                                       training_data=training_data,
                                       collate_fn=collate_fn)
        import os as _os
        if _os.environ.get("DS_AUTOTUNING") in ("tune", "run"):
            log_dist("warning: --autotuning is not supported for the hybrid engine; "
                     "the flag is ignored")
        return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler
    if isinstance(model, PipelineModule):
        # reference dispatches PipelineEngine for PipelineModule models
        # (__init__.py:158)
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(pipeline=model,
                                config=ds_config,
                                optimizer=optimizer,
                                loss_fn=loss_fn,
                                lr_scheduler=lr_scheduler,
                                topology=topology,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                collate_fn=collate_fn)
    else:
        engine = DeepSpeedEngine(model=model,
                                 config=ds_config,
                                 optimizer=optimizer,
                                 loss_fn=loss_fn,
                                 lr_scheduler=lr_scheduler,
                                 topology=topology,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 collate_fn=collate_fn)

    # --autotuning tune|run (reference launcher/runner.py:358): the tuner
    # needs real batch shapes, so it engages on the engine's first
    # initialize_state — see DeepSpeedEngine._maybe_autotune
    import os
    mode = os.environ.get("DS_AUTOTUNING", "")
    raw_cfg = ds_config.raw_dict
    if not isinstance(model, PipelineModule):
        from deepspeed_tpu.autotuning.config import get_autotuning_config
        at = get_autotuning_config(raw_cfg)
        if mode in ("tune", "run") or at.enabled:
            engine._autotune = (mode or "run", dict(raw_cfg))
    elif mode in ("tune", "run"):
        log_dist("warning: --autotuning is not supported for PipelineModule models; "
                 "the flag is ignored")
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def add_config_arguments(parser: argparse.ArgumentParser):
    """Reference ``__init__.py:246``: add --deepspeed flags to an argparser."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to wrap scripts)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true", help=argparse.SUPPRESS)
    group.add_argument("--deepscale_config", default=None, type=str, help=argparse.SUPPRESS)
    return parser
