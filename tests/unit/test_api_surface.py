"""Top-level import surface pins (reference ``deepspeed/__init__.py``
exports): every public name a reference user reaches for must resolve."""
import pytest

import deepspeed_tpu as ds

REFERENCE_EXPORTS = [
    "initialize", "init_inference", "add_config_arguments",
    "zero", "comm", "ops", "moe", "pipe", "module_inject",
    "DeepSpeedEngine", "DeepSpeedConfig", "DeepSpeedConfigError",
    "DeepSpeedHybridEngine", "PipelineEngine", "PipelineModule",
    "InferenceEngine", "DeepSpeedInferenceConfig",
    "DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig",
    "checkpointing", "get_accelerator", "init_distributed",
    "OnDevice", "logger", "log_dist", "__version__",
    "DeepSpeedOptimizer", "ZeROOptimizer", "DeepSpeedOptimizerCallable",
    "DeepSpeedSchedulerCallable", "ADAM_OPTIMIZER", "LAMB_OPTIMIZER",
    "add_tuning_arguments", "replace_transformer_layer",
    "revert_transformer_layer", "HAS_TRITON", "version",
    "__version_major__", "runtime",
]


@pytest.mark.parametrize("name", REFERENCE_EXPORTS)
def test_reference_export_resolves(name):
    assert getattr(ds, name) is not None


def test_unknown_attribute_raises():
    with pytest.raises(AttributeError, match="no attribute"):
        ds.definitely_not_an_export


def test_zero_namespace():
    assert hasattr(ds.zero, "Init")
    assert hasattr(ds.zero, "GatheredParameters")
    assert ds.zero.ZeroParamStatus.AVAILABLE.value == 1  # reference enum parity
    assert ds.zero.ZeroParamStatus.NOT_AVAILABLE.value == 2
    assert ds.zero.ZeroParamStatus.INFLIGHT.value == 3


def test_round4_surfaces_resolve():
    """Round-4 additions under their reference import paths."""
    from deepspeed_tpu.checkpoint import (get_mpu_ranks, meg_2d_parallel_map,
                                          reshape_meg_2d_parallel)
    from deepspeed_tpu.compression.compress import (init_compression,
                                                    student_initialization)
    from deepspeed_tpu.compression.scheduler import compression_scheduler
    from deepspeed_tpu.elasticity import DSElasticAgent, touch_heartbeat
    from deepspeed_tpu.model_implementations import DSUNet, DSVAE
    from deepspeed_tpu.model_implementations.diffusers.unet import DSUNet as U2
    from deepspeed_tpu.model_implementations.diffusers.vae import DSVAE as V2
    from deepspeed_tpu.runtime.zero.param_offload import (PartitionedParamSwapper,
                                                          stream_in)
    from deepspeed_tpu.runtime.swap_tensor.optimizer_swapper import NVMeAdam
    assert U2 is DSUNet and V2 is DSVAE
    for obj in (reshape_meg_2d_parallel, meg_2d_parallel_map, get_mpu_ranks,
                init_compression, student_initialization, compression_scheduler,
                DSElasticAgent, touch_heartbeat, PartitionedParamSwapper,
                stream_in, NVMeAdam):
        assert obj is not None


# ---------------------------------------------------------------------------
# the traced program is a function of configuration (docs/API.md): the
# packages that build programs read nothing from the environment
# ---------------------------------------------------------------------------
PROGRAM_PACKAGES = ("models", "moe", "ops", "inference/serving", "runtime/pipe")

#: (file, what it reads): paths and a lint budget, not program choices
ENVIRONMENT_READS_ALLOWED = {
    ("ops/op_builder/builder.py", "'DS_BUILD_CACHE'"),      # where host ops are built
    ("ops/op_builder/builder.py", "'CXX'"),                 # the compiler that builds them
    ("inference/serving/scheduler.py", "HEARTBEAT_ENV"),    # a supervisor's liveness file
    ("runtime/pipe/engine.py", "'DS_PIPE_ACT_BUDGET_MB'"),  # graft-lint's budget (R010)
}


def _package_sources(package):
    import os
    root = os.path.join(os.path.dirname(ds.__file__), *package.split("/"))
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                rel = os.path.relpath(path, os.path.dirname(ds.__file__)).replace(os.sep, "/")
                with open(path) as f:
                    yield rel, f.read()


def _environment_reads(source):
    """What a module reads of ``os.environ``: the key's source text for a
    ``.get(key)`` / ``[key]`` / ``os.getenv(key)``, ``<environ>`` for any
    other use of it."""
    import ast
    tree = ast.parse(source)
    keyed, reads = set(), []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            f = node.func
            environ_get = (f.attr in ("get", "pop", "setdefault")
                           and ast.unparse(f.value) == "os.environ")
            if (environ_get or ast.unparse(f) == "os.getenv") and node.args:
                reads.append(ast.unparse(node.args[0]))
                keyed.add(id(f.value) if environ_get else id(f))
        elif isinstance(node, ast.Subscript) and ast.unparse(node.value) == "os.environ":
            reads.append(ast.unparse(node.slice))
            keyed.add(id(node.value))
    for node in ast.walk(tree):
        if id(node) in keyed:
            continue
        if isinstance(node, ast.Attribute) and ast.unparse(node) in ("os.environ", "os.getenv"):
            reads.append("<environ>")
        if isinstance(node, ast.ImportFrom) and node.module == "os" and any(
                a.name in ("environ", "getenv") for a in node.names):
            reads.append("<environ>")
    return reads


@pytest.mark.parametrize("package", PROGRAM_PACKAGES)
def test_program_packages_do_not_read_the_environment(package):
    found = {(rel, key) for rel, source in _package_sources(package)
             for key in _environment_reads(source)}
    allowed = {(rel, key) for rel, key in ENVIRONMENT_READS_ALLOWED
               if rel.startswith(package + "/")}
    assert found == allowed


def test_models_do_not_import_inference():
    import ast
    for rel, source in _package_sources("models"):
        for node in ast.walk(ast.parse(source)):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import)
                     else [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
            assert not [n for n in names if n.startswith("deepspeed_tpu.inference")], rel
