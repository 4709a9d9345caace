"""The tick rooflines of the six newest serve cells divide a least time by
the whole tick (the ``tick`` span's p50: host time and ``device_wait``
together), not by the ``device_wait`` span alone, which shrinks with the
program under an unchanged host until the share passes 100."""

import importlib
import json

import pytest

from benchmarks.lib import harness, program_spans
from benchmarks.lib.harness import REPO_ROOT, Cell, load_json
from deepspeed_tpu.utils import trace

# ticks library -> the cell whose readers use it
LIBS = {"olmoe_ticks": "serve-olmoe-1b-7b-agent-sat",
        "nemotron_h_ticks": "serve-nemotron-3-super-reason-sat",
        "joyai_llm_flash_ticks": "serve-joyai-llm-flash-longdoc-sat",
        "dots3_note_ticks": "serve-dots3-note-prev-longctx-sat",
        "laguna_ticks": "serve-laguna-xs2-mixedlen-sat",
        "ouro_ticks": "serve-ouro-2.6b-mathword-sat"}
SHAPE = {"ticks": 100.0, "tokens": 24.0, "sequences": 24.0, "kv_positions": 9000.0,
         "positions": 9000.0, "full_positions": 9000.0, "window_positions": 4000.0}


@pytest.fixture
def ring_of_ticks(monkeypatch):
    """A recorder holding steady ticks of 10 ms, 6 of them ``device_wait``:
    decode ticks, and prefill ticks twice as long."""
    rec = trace.Recorder()
    monkeypatch.setattr(trace, "_RECORDER", rec)
    for uid in range(4):       # two requests of set-up, two steady
        rec.record("queue_wait", 0.0, 0.001 * uid, uid, "s")
    now = 1.0
    for uid in range(10, 30):
        scale = 2 if uid % 4 == 0 else 1
        rec._append("device_wait", now + 0.002 * scale, now + 0.008 * scale, ("tick",), uid, "s",
                    None)
        rec._append("tick", now, now + 0.010 * scale, (), uid, "s",
                    "prefill" if scale == 2 else "decode")
        now += 0.010 * scale
    return rec


def test_tick_ms_p50_is_the_whole_tick_by_kind(ring_of_ticks):
    assert program_spans.tick_ms_p50("decode") == pytest.approx(10.0)
    assert program_spans.tick_ms_p50("prefill") == pytest.approx(20.0)
    assert program_spans.tick_ms_p50("verify") is None
    assert program_spans.device_wait_ms_p50("decode") == pytest.approx(6.0)


@pytest.mark.parametrize("kind, want", [("decode", 50.0), ("prefill", 25.0)])
@pytest.mark.parametrize("lib", list(LIBS))
def test_a_least_time_of_5_over_a_tick_of_10_reads_50_not_83(ring_of_ticks, monkeypatch, capsys,
                                                              lib, kind, want):
    """``tick_ms`` 10 with ``device_wait`` 6 and a least time of 5: 50%, where
    the parent's division by the wait read 83; the log line carries what was
    divided by under its own name."""
    module = importlib.import_module("benchmarks.lib." + lib)
    monkeypatch.setattr(module, "tick_shape", lambda *a, **k: dict(SHAPE))
    monkeypatch.setattr(module, "tick_least_ms", lambda *a, **k: (5.0, "bytes", 1.0, 2.0))
    cell = Cell(REPO_ROOT, load_json(REPO_ROOT, "BENCHMARK.json"), LIBS[lib])
    ctx = {"cell": cell, "peaks": {"flops": 1.0, "bytes": 1.0}, "counters": {}}
    assert module.tick_roofline_pct(ctx, kind) == pytest.approx(want)
    logged = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["tick_roofline"]
    assert logged["kind"] == kind and "device_wait_ms_p50" not in logged
    assert 100.0 * logged["least_ms"] / logged["tick_ms_p50"] == pytest.approx(want)


@pytest.mark.parametrize("lib", list(LIBS))
def test_no_ticks_of_the_kind_no_share(monkeypatch, lib):
    monkeypatch.setattr(trace, "_RECORDER", trace.Recorder())
    module = importlib.import_module("benchmarks.lib." + lib)
    monkeypatch.setattr(module, "tick_shape", lambda *a, **k: dict(SHAPE))
    cell = Cell(REPO_ROOT, load_json(REPO_ROOT, "BENCHMARK.json"), LIBS[lib])
    ctx = {"cell": cell, "peaks": {"flops": 1.0, "bytes": 1.0}, "counters": {}}
    assert module.tick_roofline_pct(ctx, "decode") is None
    assert module.tick_roofline_pct(dict(ctx, peaks=None), "decode") is None


@pytest.mark.parametrize("counters, says", [
    ({}, "holds no device operation"),
    ({"queue_at_close": 0}, "queue was empty at the window's close"),
    ({"queue_at_close": 37}, "held 37 requests at the window's close")])
def test_an_empty_traced_slice_says_whether_the_queue_had_run_dry(counters, says):
    message = harness._no_device_operation(counters)
    assert message.startswith("the traced window holds no device operation") and says in message
