"""Flops profiler tests (reference ``profiling/flops_profiler/profiler.py:27``):
enabling the config must produce a real report — no more silently-ignored
``flops_profiler`` block."""

import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config


def _engine(tmp_path, **fp_overrides):
    cfg = get_gpt2_config("test", n_embd=32, n_head=2, n_positions=32)
    fp = {"enabled": True, "profile_step": 2, "detailed": True}
    fp.update(fp_overrides)
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "flops_profiler": fp,
    })
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    return engine, batch


def test_profiler_writes_report_at_profile_step(tmp_path):
    out = str(tmp_path / "flops.txt")
    engine, batch = _engine(tmp_path, output_file=out)
    engine.train_batch(batch)
    assert not os.path.exists(out), "report written before profile_step"
    engine.train_batch(batch)  # global step 2 == profile_step
    assert os.path.exists(out)
    report = open(out).read()
    assert "DeepSpeed Flops Profiler" in report
    assert "params (model total)" in report
    assert "train-step flops per device" in report
    # per-module table present when detailed
    assert "Per-module profile" in report


def test_profiler_flops_are_plausible(tmp_path):
    out = str(tmp_path / "flops.txt")
    engine, batch = _engine(tmp_path, output_file=out)
    engine.train_batch(batch)
    engine.train_batch(batch)
    report = open(out).read()
    # the tiny test model still runs millions of flops per step; the line
    # must carry a parsed magnitude, not zero
    line = [l for l in report.splitlines() if l.startswith("train-step flops")][0]
    value = line.split(":")[1].strip()
    assert not value.startswith("0.00"), line


def test_profiler_module_table_from_flax(tmp_path):
    from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfiler
    import jax.numpy as jnp

    cfg = get_gpt2_config("test", n_embd=32, n_head=2, n_positions=32)
    model = GPT2LMHeadModel(cfg)
    prof = FlopsProfiler(model)
    table = prof.module_table(jnp.zeros((1, 16), jnp.int32))
    assert "flops" in table and "GPT2LMHeadModel" in table


def test_get_model_profile_standalone():
    """Reference get_model_profile surface: (flops, macs, params) for one
    forward without an engine, numbers consistent with each other."""
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.profiling.flops_profiler import get_model_profile

    model = GPT2LMHeadModel(get_gpt2_config("test"))
    flops, macs, params = get_model_profile(model, input_shape=(2, 16),
                                            print_profile=False)
    assert flops > 0 and macs == flops // 2 and params > 0
    # doubling the batch ~doubles fwd flops
    flops2, _, _ = get_model_profile(model, input_shape=(4, 16), print_profile=False)
    assert 1.5 < flops2 / flops < 2.5
    fs, ms, ps = get_model_profile(model, input_shape=(2, 16), print_profile=False,
                                   as_string=True)
    assert all(isinstance(x, str) for x in (fs, ms, ps))
