"""XLA trace capture window (trace_profiler config). The reference's
torch-profiler loop wrap has no config surface — ours does."""

import glob
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config


def test_trace_window_writes_profile(tmp_path):
    out = str(tmp_path / "trace")
    cfg = get_gpt2_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "trace_profiler": {"enabled": True, "start_step": 2, "num_steps": 1,
                           "output_dir": out},
    })
    batch = {"input_ids": np.arange(8 * 32, dtype=np.int32).reshape(8, 32) % cfg.vocab_size}
    engine.initialize_state(batch)
    for _ in range(4):
        engine.train_batch(batch)
    assert not getattr(engine, "_trace_active", False), "trace window left open"
    # jax writes plugins/profile/<run>/*.xplane.pb under the log dir
    found = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    assert found, f"no xplane trace written under {out}"


def test_trace_window_inside_fused_stack(tmp_path):
    """start_step strictly inside a train_batches stack must still open the
    window (window granularity = dispatch granularity)."""
    out = str(tmp_path / "fused_trace")
    cfg = get_gpt2_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "trace_profiler": {"enabled": True, "start_step": 2, "num_steps": 1,
                           "output_dir": out},
    })
    stack = {"input_ids": np.tile(np.arange(8 * 32, dtype=np.int32).reshape(1, 8, 32) % cfg.vocab_size,
                                  (4, 1, 1))}
    engine.initialize_state({"input_ids": stack["input_ids"][0]})
    engine.train_batches(stack)  # steps 1..4; window [2,3) intersects
    assert not getattr(engine, "_trace_active", False)
    found = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    assert found, f"no xplane trace written under {out}"


def test_trace_window_holds_the_recorders_spans_and_is_announced(tmp_path):
    """In the window the ``trace_profiler`` block opens, the engine's host
    phases lie on the host plane under ``ds:``, and with the telemetry sink
    on the window's start and stop are events of the run's log."""
    from jax.profiler import ProfileData

    from deepspeed_tpu.runtime.telemetry import read_events
    from deepspeed_tpu.utils import trace

    out = str(tmp_path / "trace")
    cfg = get_gpt2_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "trace_profiler": {"enabled": True, "start_step": 2, "num_steps": 1,
                           "output_dir": out},
        "telemetry": {"enabled": True, "output_path": str(tmp_path), "job_name": "run"},
    })
    batch = {"input_ids": np.arange(8 * 32, dtype=np.int32).reshape(8, 32) % cfg.vocab_size}
    for _ in range(3):
        engine.train_batch(batch)
    engine.telemetry.close()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events if ev.name.startswith(trace.PREFIX)}
    assert {"ds:train_batch", "ds:dispatch", "ds:device_wait"} <= names
    events = read_events(os.path.join(str(tmp_path), "run", "telemetry.jsonl"))
    assert [e["phase"] for e in events if e["event"] == "xla_trace"] == ["start", "stop"]

