"""The per-layer metrics that read the program's own recorder
(``deepspeed_tpu/utils/trace.py``): each has its entry and its reader, each
reader finds nothing in an empty ring and a number after the CPU rehearsal
of its cell. Nothing here is a measurement: the platform is the CPU."""

import json
import os

import pytest

from benchmarks.lib import harness, program_spans
from deepspeed_tpu.utils import trace

SEED = 2 ** 31 + 29

# metric -> (source, layer, moves, real cells among those the entry lists, the rehearsal's cells);
# ``test_bench_manifest.py`` holds each listed cell to the entry's ``moves`` and kind
METRICS = {
    "sched_host_ms_p50_chat": ("program_span", "serving scheduler", "itl_p95_ms",
                               ["serve-gpt2-medium-chat"], ["t-chat"]),
    "sched_host_ms_p50_sat": ("program_span", "serving scheduler", "serve_total_tok_s",
                              ["serve-gpt2-medium-docs-sat"], ["t-docs"]),
    "queue_wait_p90_ms": ("program_span", "serving scheduler", "itl_p95_ms",
                          ["serve-gpt2-medium-chat"], ["t-chat"]),
    "prefill_wait_p90_ms": ("program_span", "serving scheduler", "itl_p95_ms",
                            ["serve-gpt2-medium-chat"], ["t-chat"]),
    "decode_device_wait_ms_p50": ("program_span", "serving programs", "itl_p95_ms",
                                  ["serve-gpt2-medium-chat"], ["t-chat"]),
    "prefill_device_wait_ms_p50": ("program_span", "serving programs", "serve_total_tok_s",
                                   ["serve-gpt2-medium-docs-sat"], ["t-docs"]),
    "prefill_fill_pct_sat": ("program_counter", "serving programs", "serve_total_tok_s",
                             ["serve-gpt2-medium-docs-sat"], ["t-docs"]),
    "train_host_ms_p50": ("program_span", "training engine", "train_tok_s_chip",
                          ["train-gpt2-medium-seq1k", "train-gpt2-xl-zero3-x4"],
                          ["t-train", "t-train-x4"]),
}


def _reader(root, name):
    return harness.load_module(root, harness.BENCH_DIR, "layer_metrics", name + ".py")


@pytest.fixture(scope="module")
def traced(bench_copy):
    """One traced run of every rehearsal cell with its output lines, on a
    recorder of its own, so that what the readers see is this run's."""
    root, manifest = bench_copy
    out = {}
    for cell in ("t-chat", "t-docs", "t-train", "t-train-x4"):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(trace, "_RECORDER", trace.Recorder())
            logged = []
            patch.setattr(harness, "log", lambda **fields: logged.append(fields))
            line = harness.run_cell(root, manifest, cell, SEED, 0.5, 1, require_tpu=False)
            out[cell] = (line, logged, trace.recorder())
    return out


@pytest.mark.parametrize("name", list(METRICS))
def test_metric_has_its_entry_and_its_reader(name):
    with open(os.path.join(harness.REPO_ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    source, layer, moves, cells, _ = METRICS[name]
    assert (entry["source"], entry["layer"], entry["moves"]) == (source, layer, moves)
    assert set(cells) <= set(entry["workloads"]) <= {w["name"] for w in manifest["workloads"]}
    assert entry["unit"] == ("%" if name.endswith("_pct_sat") else "ms")
    assert entry["better"] == ("higher" if name.endswith("_pct_sat") else "lower")
    reader = _reader(harness.REPO_ROOT, name)
    assert callable(reader.read) and len(reader.__doc__) > 80     # says what it reads and leaves out


@pytest.mark.parametrize("name", list(METRICS))
def test_reader_finds_nothing_in_an_empty_ring(name, monkeypatch):
    monkeypatch.setattr(trace, "_RECORDER", trace.Recorder())
    assert _reader(harness.REPO_ROOT, name).read({"cell": None, "counters": {}, "spans": {},
                                                 "trace": None, "peaks": None}) is None


@pytest.mark.parametrize("name", list(METRICS))
def test_reader_gives_a_number_after_the_rehearsal_of_its_cell(traced, name):
    for cell in METRICS[name][4]:
        line = traced[cell][0]
        assert line["correct"] is True and line["failed"] == 0
        metric = line["metrics"][name]
        assert isinstance(metric["value"], float) and metric["value"] >= 0
        if name.endswith("_pct_sat"):
            assert 0 < metric["value"] <= 100


def test_readers_do_not_raise_where_the_program_has_no_recorder(monkeypatch):
    """At the parent commit ``deepspeed_tpu.utils.trace`` does not exist:
    the import fails, the readers return None and the line leaves the
    metrics out."""
    import sys
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.utils.trace", None)   # import raises ImportError
    import deepspeed_tpu.utils
    monkeypatch.delattr(deepspeed_tpu.utils, "trace", raising=False)
    assert program_spans.ring() == ([], {})
    for name in METRICS:
        assert _reader(harness.REPO_ROOT, name).read({}) is None


def test_the_split_by_phase_is_in_the_output(traced):
    line, logged, _ = traced["t-chat"]
    (split,) = [f["program_tick_split"] for f in logged if "program_tick_split" in f]
    assert {"prefill", "decode"} <= set(split)
    for kind in ("prefill", "decode"):
        phases = split[kind]["phases"]
        assert {"admit", "build_inputs", "stamp", "dispatch", "device_wait", "commit",
                "heartbeat"} <= set(phases)
        assert all(p["p50_ms"] >= 0 and p["sum_s"] >= 0 for p in phases.values())
        # inside and outside agree: host time and the read-back make up the tick
        assert sum(p["sum_s"] for p in phases.values()) <= split[kind]["tick_sum_s"]
    kinds = {f["program_ticks_of_kind"]["kind"]: f["program_ticks_of_kind"]
             for f in logged if "program_ticks_of_kind" in f}
    decode = kinds["decode"]
    assert decode["host_ms_p50"] + decode["device_wait_ms_p50"] == \
        pytest.approx(decode["tick_ms_p50"], rel=0.25)
    waits = {f["program_request_wait"]["span"] for f in logged if "program_request_wait" in f}
    assert waits == {"queue_wait", "prefill_wait"}
    (steps,) = [f["program_step_split"] for f in traced["t-train"][1] if "program_step_split" in f]
    assert {"timer_sync", "batch_stage", "dispatch", "device_wait", "post_step",
            "train_batch"} <= set(steps["phases"])


def test_set_up_idle_ticks_and_what_follows_the_profilers_start_are_left_out(traced):
    _, _, rec = traced["t-chat"]
    found_ticks = [r for r in rec.records() if r.name == "tick"]
    admitted = [r for r in rec.records() if r.name == "queue_wait"]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace, "_RECORDER", rec)
        steady = program_spans.serving()
    assert all(t["kind"] != "idle" for t in steady["ticks"])
    working = [r for r in found_ticks if r.kind != "idle"]
    set_up = [r for r in working if r.seq < admitted[2].seq]
    assert set_up, "the two checked requests ran ticks of their own"
    assert 0 < len(steady["ticks"]) <= len(working) - len(set_up)
    # the checked two and the first of the pre-roll have no wait in the sample
    assert 0 < len(steady["queue_wait_ms"]) <= len(admitted) - 3
    assert len(steady["prefill_wait_ms"]) <= len(steady["queue_wait_ms"])


def test_a_long_gap_between_ticks_ends_the_sample(monkeypatch):
    rec = trace.Recorder()
    monkeypatch.setattr(trace, "_RECORDER", rec)
    t = [0.0]

    def tick(uid, kind="decode", gap=0.001):
        t[0] += gap
        start = t[0]
        rec._append("device_wait", start + 0.001, start + 0.004, ("tick",), uid, "s", None)
        t[0] = start + 0.005
        rec._append("tick", start, t[0], (), uid, "s", kind)

    for uid in range(3):
        rec.record("queue_wait", 0.0, float(uid > 1), uid, "s")    # two of set-up, one after
    rec.record("queue_wait", 0.0, 2.0, 3, "s")
    for uid in range(1, 5):
        tick(uid)
    tick(5, gap=program_spans.STALL_S + 0.1)       # the profiler started here
    tick(6)
    steady = program_spans.serving()
    assert len(steady["ticks"]) == 4
    assert steady["ticks"][0]["host_ms"] == pytest.approx(2.0)
    assert steady["ticks"][0]["phases"]["device_wait"] == pytest.approx(3.0)
    assert steady["queue_wait_ms"] == [2000.0]     # request 2 came to the empty server
