"""The rule (docs/API.md, "What decides the traced program"): the program a
module traces is a function of that module's own configuration and its call
arguments. Nothing read from the environment, and no module-level variable
another layer installed, changes it.

Held here on the toy train step, decode program, MoE layer and pipeline
step: the nine variables that used to reach them change nothing, an engine's
init leaves nothing behind for the next engine, and two schedulers on one
engine each serve what their own configuration says.
"""

import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flax.linen as nn

import deepspeed_tpu
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (FINISHED, ContinuousBatchingScheduler,
                                             Request, ServingConfig)
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
from deepspeed_tpu.moe.sharded_moe import MOELayer
from deepspeed_tpu.ops.pallas import attention_geometry as ag
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.runtime.pipe.module import PipelineModule

SEQ = 64
WINNERS_FILE = "<a winners file naming the toy step's attention shape>"

#: variable -> (the toy program it used to reach, a value that is not the default)
DELETED_VARIABLES = {
    "DS_MOE_ROUTE": ("moe", "dense"),
    "DS_MOE_KERNEL": ("moe", "pallas"),
    "DS_SERVE_WQ": ("decode", "int8"),
    "DS_SERVE_PREFIX_CACHE": ("decode", "off"),
    "DS_ATTN_BLOCKS": ("train", "block_q=16,block_k=16"),
    "DS_ATTN_CACHE": ("train", WINNERS_FILE),
    "DS_REMAT_POLICY": ("train", "dots_saveable"),
    "DS_LMHEAD_CHUNK": ("train", "16"),
    "DS_PIPE_SCHEDULE": ("pipe", "gpipe"),
}


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for name in DELETED_VARIABLES:
        monkeypatch.delenv(name, raising=False)
    set_topology(None)
    yield
    set_topology(None)


def _one_device():
    return MeshTopology(devices=jax.devices()[:1])


def _train_engine(model, **blocks):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, topology=_one_device(),
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}, **blocks})
    return engine


def _step_text(engine, batch=2):
    ids = np.zeros((batch, SEQ), np.int32)
    return engine.traced_programs({"input_ids": ids})["train_step"]["lower"]().as_text()


def _train_text():
    model = GPT2LMHeadModel(get_gpt2_config("test", attention_backend="flash"))
    return _step_text(_train_engine(model))


def _serve_engine():
    cfg = get_gpt2_config("test", n_layer=2, n_positions=128)
    engine = InferenceEngine(GPT2LMHeadModel(cfg),
                             DeepSpeedInferenceConfig(replace_with_kernel_inject=False),
                             topology=MeshTopology(tensor=1, data=1, fsdp=1,
                                                   devices=jax.devices()[:1]))
    return engine, cfg


def _decode_text(sched):
    toks = np.zeros(sched.slots, np.int32)
    return sched.fns["decode"].lower(sched._serve_params, sched._cache, toks).as_text()


def _decode_program():
    """The decode program of a default scheduler, with what it says it serves."""
    engine, _ = _serve_engine()
    sched = ContinuousBatchingScheduler(engine, ServingConfig(slots=4))
    stats = sched.stats()
    return (_decode_text(sched), stats["weight_dtype"], stats["prefix_cache"],
            sched.pool.prefix_cache)


class _Expert(nn.Module):

    @nn.compact
    def __call__(self, x, deterministic=True):
        return nn.Dense(x.shape[-1], use_bias=False)(x)


def _moe_text():
    layer = MOELayer(expert=_Expert(), model_dim=8, num_experts=4, k=1, min_capacity=1)
    x = jnp.zeros((2, 16, 8), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    apply = lambda v, xx: layer.apply(v, xx, mutable=["intermediates"])[0][0]  # noqa: E731
    return jax.jit(apply).lower(variables, x).as_text()


def _pipe_text():
    cfg = get_gpt2_config("test", n_layer=2)
    topo = MeshTopology(pipe=2, data=1, devices=jax.devices()[:2])
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=pipe, topology=topo,
        config={"train_batch_size": 8, "gradient_accumulation_steps": 4,
                "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    return _step_text(engine, batch=8)


PROGRAMS = {"train": _train_text, "decode": _decode_program, "moe": _moe_text,
            "pipe": _pipe_text}


@functools.lru_cache(maxsize=None)
def _with_the_variable_unset(program):
    return PROGRAMS[program]()


@pytest.mark.parametrize("name", sorted(DELETED_VARIABLES))
def test_program_ignores_the_environment(name, monkeypatch, tmp_path):
    program, value = DELETED_VARIABLES[name]
    want = _with_the_variable_unset(program)
    if value is WINNERS_FILE:
        value = str(tmp_path / "attention_blocks.json")
        for dtype in (jnp.float32, jnp.bfloat16):
            ag.store_winner(ag.signature(SEQ, SEQ, 16, 4, 2, True, jnp.dtype(dtype)),
                            ag.AttentionGeometry(block_q=16, block_k=16), path=value)
    monkeypatch.setenv(name, value)
    set_topology(None)
    assert PROGRAMS[program]() == want


def test_engine_init_leaves_no_process_state():
    """An engine with "attention" and "moe" blocks, then one without: the
    first one's step, traced anew, is the text it was before the second
    existed, and the second's is the default program."""
    model = GPT2LMHeadModel(get_gpt2_config(
        "test", attention_backend="flash", moe_num_experts=4, moe_layer_freq=2))
    first = _train_engine(model, attention={"block_q": 16, "block_k": 16},
                          moe={"route": "dense", "kernel": "xla"})
    before = _step_text(first)
    second = _train_engine(model)
    plain = _step_text(second)
    assert second.module is model and plain != before
    assert (first.module.config.moe_route, model.config.moe_route) == ("dense", "sorted")
    assert (first.module.config.attention_blocks, model.config.attention_blocks) == (
        "block_q=16,block_k=16", None)
    jax.clear_caches()      # so that the first one's step is traced again, not recalled
    assert _step_text(first) == before
    assert _step_text(second) == plain


@contextlib.contextmanager
def _compiles():
    """The names of the programs compiled inside the block."""
    seen = []

    def listener(event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def test_two_schedulers_keep_their_own_weight_dtype():
    """An int8 and an fp scheduler on one engine, ticks interleaved: each
    emits what it emits alone, each says what it serves, and no tick
    compiles anything after warm-up."""
    engine, cfg = _serve_engine()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (5, 12, 9)]

    def build(wd):
        sched = ContinuousBatchingScheduler(engine, ServingConfig(slots=4, weight_dtype=wd))
        sched.warmup()
        reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
        for r in reqs:
            sched.submit(r)
        return sched, reqs

    alone = {}
    for wd in ("int8", "fp"):
        sched, reqs = build(wd)
        sched.run_until_drained()
        alone[wd] = [r.output for r in reqs]

    pairs = {wd: build(wd) for wd in ("int8", "fp")}
    texts = {wd: _decode_text(sched) for wd, (sched, _) in pairs.items()}
    assert texts["int8"] != texts["fp"]
    with _compiles() as compiled:
        for _ in range(500):
            if all(r.done for _, reqs in pairs.values() for r in reqs):
                break
            for sched, _ in pairs.values():
                sched.step()
    assert compiled == []
    for wd, (sched, reqs) in pairs.items():
        assert all(r.state == FINISHED for r in reqs)
        assert [r.output for r in reqs] == alone[wd]
        stats = sched.stats()
        assert stats["weight_dtype"] == wd
        assert not [k for k in stats if k.endswith("_source")]
        assert _decode_text(sched) == texts[wd]
