"""graft-trace unit tests: metric primitives, JSONL sink semantics, span
nesting, the engine's end-to-end event stream, and the
``tools/trace_report.py`` Chrome-trace / drift round trip."""

import json
import os
import sys

import numpy as np
import pytest

from deepspeed_tpu.runtime.telemetry import (DEFAULT_LATENCY_BOUNDS, Histogram,
                                             JsonlSink, RuntimeTelemetry,
                                             TELEMETRY_SCHEMA_VERSION, read_events)
from deepspeed_tpu.utils import trace

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", "..", ".."))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_histogram_percentiles_and_merge():
    h = Histogram()
    for v in [0.001] * 90 + [0.1] * 10:
        h.record(v)
    assert h.count == 100 and h.min == 0.001 and h.max == 0.1
    assert 0.0005 < h.percentile(50) < 0.0021  # lands in the 1ms bucket
    assert 0.05 < h.percentile(99) <= 0.1
    # mergeable: same bounds add counts; different bounds refuse loudly
    other = Histogram()
    for _ in range(100):
        other.record(0.1)
    h.merge(other)
    assert h.count == 200 and 0.05 < h.percentile(50) <= 0.1
    with pytest.raises(ValueError):
        h.merge(Histogram(bounds=[1.0, 2.0]))
    # snapshot is sparse and JSON-able
    snap = h.snapshot()
    json.dumps(snap)
    assert snap["count"] == 200 and "p99" in snap and len(snap["buckets"]) <= 3


def test_histogram_empty_and_out_of_range():
    h = Histogram()
    assert h.percentile(50) is None and h.mean is None and h.snapshot() == {"count": 0}
    h.record(0.0)  # below the first bound
    h.record(1e9)  # beyond the last bound (open-ended bucket)
    assert h.count == 2 and h.percentile(99) <= 1e9
    assert len(h.counts) == len(DEFAULT_LATENCY_BOUNDS) + 1


# ---------------------------------------------------------------------------
# sink
# ---------------------------------------------------------------------------
def test_sink_rank_gating_and_corrupt_tail(tmp_path):
    path = str(tmp_path / "t.jsonl")
    JsonlSink(path, rank=1).write({"event": "x"})
    assert not os.path.exists(path), "non-zero rank must not write"
    sink = JsonlSink(path, rank=0)
    sink.write({"event": "a", "n": 1})
    # non-JSON payload leaves coerce to strings — written, never raising
    sink.write({"event": "coerced", "bad": object(), "arr": np.arange(2)})
    sink.close()
    with open(path, "a") as fh:
        fh.write('{"event": "torn')  # crashed-writer tail
    events = read_events(path)
    assert [e["event"] for e in events] == ["a", "coerced"]
    assert events[1]["arr"] == [0, 1] and isinstance(events[1]["bad"], str)
    assert all("t" in e for e in events)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_drain(monkeypatch):
    """The telemetry's window drain over the process's recorder: its own
    source's records only, at most ``max_buffered`` events a window (the
    rest counted, the histograms never dropping), then an empty window."""
    monkeypatch.setattr(trace, "_RECORDER", trace.Recorder())

    class Cfg:
        enabled = False
        max_buffered_spans = 3

    tel, other = RuntimeTelemetry(Cfg()), RuntimeTelemetry(Cfg())
    assert tel.source != other.source
    with tel.span("outer", 7):
        with tel.span("inner", 7):
            pass
        with other.span("not_mine"):
            pass
    assert tel.last_span in ("inner", "outer", "not_mine")
    with tel.span("third"):
        pass
    with tel.span("dropped"):  # over the window's cap: counted, not written
        pass
    events, hists, dropped = tel._drain()
    assert [e["name"] for e in events] == ["inner", "outer", "third"]
    assert events[0]["path"] == "outer" and events[0]["depth"] == 1 and events[0]["uid"] == 7
    assert events[0]["ts"] > 1e9 and events[0]["dur_s"] >= 0     # epoch seconds, for trace_report
    assert dropped == 1
    assert set(hists) == {"outer", "inner", "third", "dropped"}  # hist never drops
    assert tel._drain() == ([], {}, 0)
    assert [e["name"] for e in other._drain()[0]] == ["not_mine"]


# ---------------------------------------------------------------------------
# engine end-to-end + trace_report round trip
# ---------------------------------------------------------------------------
def _train_run(tmp_path, n_steps=3, extra_cfg=None):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test")
    config = {"train_batch_size": 8,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "steps_per_print": 1,
              "telemetry": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "run"}}
    config.update(extra_cfg or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config=config)
    batch = {"input_ids": np.arange(8 * 32, dtype=np.int32).reshape(8, 32) % cfg.vocab_size}
    for _ in range(n_steps):
        engine.train_batch(batch)
    engine.telemetry.sink.flush()  # steps_per_print=1: every step flushed a window
    return engine, os.path.join(str(tmp_path), "run")


def test_engine_event_stream_and_run_header(tmp_path):
    engine, run_dir = _train_run(tmp_path)
    events = read_events(os.path.join(run_dir, "telemetry.jsonl"))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start"
    for expected in ("spans", "step_window", "drift", "monitor"):
        assert expected in kinds, kinds
    header = events[0]
    assert header["schema"] == TELEMETRY_SCHEMA_VERSION
    run = header["run"]
    # provenance: config sig + versions + mesh, per the run-header contract
    assert len(run["config_sig"]) == 12 and run["model"] == "GPT2LMHeadModel"
    assert run["jax_version"] and run["jaxlib_version"]
    assert run["mesh_axes"]["data"] >= 1
    price = header["static_price"]
    assert price["flops_proxy"] > 0 and price["peak_bytes"] > 0
    assert price["peak_transient_bytes"] > 0 and price["eqns"] > 0
    # span timeline covers the real step phases
    span_names = {s["name"] for e in events if e["event"] == "spans"
                  for s in e["spans"]}
    assert {"batch_stage", "dispatch", "device_wait", "post_step"} <= span_names
    # drift windows carry the prediction and a measured ratio
    drift = [e for e in events if e["event"] == "drift"][-1]
    assert drift["predicted"]["flops_proxy"] == price["flops_proxy"]
    assert drift["ratios"]["achieved_tflops"] > 0
    # monitor events rode the bus into the JSONL (no csv/tb sink configured)
    mon = [e for e in events if e["event"] == "monitor"][-1]
    assert any(t == "Train/loss" for t, _, _ in mon["events"])


def test_train_batch_children_with_the_sink_off():
    """The recorder is on whenever the engine runs: with no ``telemetry``
    block every ``train_batch`` is a span with its five host phases as
    children, under the step's number and the engine's source."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        config={"train_batch_size": 8, "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    assert not engine.telemetry.enabled
    batch = {"input_ids": np.arange(8 * 32, dtype=np.int32).reshape(8, 32) % cfg.vocab_size}
    for _ in range(3):
        engine.train_batch(batch)
    stack = {"input_ids": np.tile(batch["input_ids"][None], (2, 1, 1))}
    engine.train_batches(stack)
    records = trace.recorder().records(engine.telemetry.source)
    steps = [r for r in records if r.name == "train_batch"]
    assert [r.uid for r in steps] == [1, 2, 3, 4]     # the fused stack is one span, from step 4
    for step in steps:
        # (what a step compiled between its phases is records of their own among them)
        children = [r for r in records if r.uid == step.uid and r.path == ("train_batch",)
                    and not r.name.startswith(("compile_", "recompile"))]
        assert [r.name for r in children] == ["timer_sync", "batch_stage", "dispatch",
                                              "device_wait", "post_step"]
        assert all(r.parent == "train_batch" for r in children)
        assert all(step.start <= r.start <= r.end <= step.end for r in children)
        assert sum(r.dur for r in children) <= step.dur


def test_the_sink_adds_no_device_sync(tmp_path, monkeypatch):
    """With the sink on, a step waits for the device where it does with it
    off: in the throughput timer's two syncs, and nowhere else."""
    import jax

    from deepspeed_tpu.utils import timer

    engine, _ = _train_run(tmp_path, n_steps=3)     # past the timer's first two, unsynced, steps
    calls = {"block_until_ready": 0, "timer": 0}
    real_block, real_sync = jax.block_until_ready, timer._device_sync

    def block(x):
        calls["block_until_ready"] += 1
        return real_block(x)

    def sync():
        calls["timer"] += 1
        return real_sync()

    monkeypatch.setattr(jax, "block_until_ready", block)
    monkeypatch.setattr(timer, "_device_sync", sync)
    cfg_vocab = engine.module.config.vocab_size
    batch = {"input_ids": np.arange(8 * 32, dtype=np.int32).reshape(8, 32) % cfg_vocab}
    for _ in range(2):
        engine.train_batch(batch)
    assert calls == {"block_until_ready": 0, "timer": 4}


def test_trace_report_round_trip_and_drift(tmp_path, capsys):
    """Acceptance: valid Chrome trace-event JSON from a real 3-step run's
    JSONL, and --drift prints predicted-vs-measured for the gpt2 run."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    _, run_dir = _train_run(tmp_path)
    out = str(tmp_path / "chrome.json")
    assert trace_report.main([run_dir, "--out", out]) == 0
    capsys.readouterr()
    trace = json.load(open(out))
    evs = trace["traceEvents"]
    assert evs, "empty chrome trace"
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] > 0
    assert {"batch_stage", "dispatch"} <= {e["name"] for e in evs if e["ph"] == "X"}
    # --drift: table + one JSON summary line with the ratios
    assert trace_report.main([run_dir, "--drift"]) == 0
    outtxt = capsys.readouterr().out
    assert "flops_proxy=" in outtxt and "achieved_tflops" in outtxt
    summary = json.loads([l for l in outtxt.splitlines()
                          if l.startswith("{")][-1])["summary"]
    assert summary["ratios"]["achieved_tflops"] > 0
    assert summary["median_step_s"] > 0


def test_trace_report_shows_the_start_and_lists_every_recompile(tmp_path, capsys):
    """The spans of the start and the compile records the recorder files
    under them are in the exported timeline (a back-dated record carries
    its enclosing span's source, so the window's drain takes it), and a
    step that compiled again is named on stderr: step, phase, function."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    engine, run_dir = _train_run(tmp_path, n_steps=2)
    shorter = {"input_ids": np.arange(8 * 16, dtype=np.int32).reshape(8, 16) % 256}
    engine.train_batch(shorter)      # another shape: step 3 compiles the step again
    engine.telemetry.sink.flush()
    out = str(tmp_path / "chrome.json")
    assert trace_report.main([run_dir, "--out", out]) == 0
    err = capsys.readouterr().err
    listed = [line for line in err.splitlines() if "recompile in unit" in line]
    assert any("unit 3 (step), phase dispatch: jit(train_step)" in line for line in listed), err
    spans = [e for e in json.load(open(out))["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"initialize", "initialize_state", "state_init", "build_step", "compile_lower",
            "compile_backend", "recompile"} <= names
    first = [e for e in spans if e["name"] == "compile_backend"
             and e["args"].get("kind") == "jit(train_step)"]
    assert [e["args"]["path"] for e in first] == ["train_batch/dispatch"] * 2     # steps 1 and 3
    (start,) = [e for e in spans if e["name"] == "initialize_state"]
    inside = [e for e in spans if e["args"]["path"].startswith("initialize_state")]
    assert inside and all(start["ts"] <= e["ts"] and e["ts"] + e["dur"] <= start["ts"]
                          + start["dur"] + 1e3 for e in inside)


def test_checkpoint_spans_and_event(tmp_path):
    engine, run_dir = _train_run(tmp_path, n_steps=2)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    engine.telemetry.flush_window(step=99)
    engine.telemetry.close()
    events = read_events(os.path.join(run_dir, "telemetry.jsonl"))
    ckpt = [e for e in events if e["event"] == "checkpoint"]
    assert ckpt and ckpt[0]["tag"] == "global_step2" and ckpt[0]["dur_s"] > 0
    span_names = {s["name"] for e in events if e["event"] == "spans"
                  for s in e["spans"]}
    assert {"ckpt_stage", "ckpt_publish"} <= span_names


def test_fused_train_batches_counts_steps(tmp_path):
    """One fused dispatch of n steps = n per-step samples (stack time / n)
    in the step histogram, with the window flushing on the cadence."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 4,
                "telemetry": {"enabled": True, "output_path": str(tmp_path),
                              "job_name": "fused"}})
    ids = np.arange(8 * 32, dtype=np.int32).reshape(1, 8, 32) % cfg.vocab_size
    stack = {"input_ids": np.tile(ids, (4, 1, 1))}
    engine.initialize_state({"input_ids": stack["input_ids"][0]})
    engine.train_batches(stack)
    engine.telemetry.sink.flush()
    events = read_events(os.path.join(str(tmp_path), "fused", "telemetry.jsonl"))
    drift = [e for e in events if e["event"] == "drift"]
    assert drift and drift[-1]["window_steps"] == 4
    window = [e for e in events if e["event"] == "step_window"][-1]
    assert window["phases"]["step"]["count"] == 4
    assert window["phases"]["dispatch"]["count"] == 1  # one fused dispatch
    assert engine.telemetry.drift_summary()["steps"] == 4
