"""The six per-layer metrics under ``setup_s`` (``benchmarks/lib/program_setup.py``):
each has its entry and its reader, each reader finds nothing on a program
that counts no set-up, and the CPU rehearsal of a serving and a training
cell reports all six, their sum under the run's ``setup_s``. Nothing here
is a measurement: the platform is the CPU."""

import json
import os
import time

import pytest

from benchmarks.lib import harness, program_setup
from deepspeed_tpu.utils import trace

SEED = 2 ** 31 + 34
# metric -> (unit, source)
METRICS = {
    "setup_trace_lower_s": ("s", "program_span"),
    "setup_backend_load_s": ("s", "program_span"),
    "setup_cache_misses": ("count", "program_counter"),
    "setup_programs_loaded": ("count", "program_counter"),
    "setup_engine_init_s": ("s", "program_span"),
    "setup_import_s": ("s", "program_span"),
}
SECONDS = ("setup_trace_lower_s", "setup_backend_load_s", "setup_engine_init_s", "setup_import_s")


def _reader(name):
    return harness.load_module(harness.REPO_ROOT, harness.BENCH_DIR, "layer_metrics",
                               name + ".py")


def _ctx():
    return {"cell": None, "counters": {}, "spans": {}, "trace": None, "peaks": None}


def _rehearse(root, manifest, cell):
    """One traced run of a rehearsal cell on a recorder of its own:
    (last line, logged lines, the recorder)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace, "_RECORDER", trace.Recorder())
        logged = []
        patch.setattr(harness, "log", lambda **fields: logged.append(fields))
        line = harness.run_cell(root, manifest, cell, SEED, 0.5, 1, require_tpu=False)
        return line, logged, trace.recorder()


@pytest.fixture(scope="module")
def traced(bench_copy):
    root, manifest = bench_copy
    return {cell: _rehearse(root, manifest, cell) for cell in ("t-chat", "t-train")}


@pytest.mark.parametrize("name", list(METRICS))
def test_metric_has_its_entry_and_its_reader(name):
    with open(os.path.join(harness.REPO_ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    unit, source = METRICS[name]
    # no ``workloads``: every start counts these, so every cell reports them, as it does ``setup_s``
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": "entry points and runner", "moves": "setup_s"}
    reader = _reader(name)
    assert callable(reader.read) and len(reader.__doc__) > 80     # says what it reads and leaves out


@pytest.mark.parametrize("name", list(METRICS))
def test_reader_finds_nothing_on_a_program_that_counts_no_set_up(name, monkeypatch):
    """As on the parent: a recorder with its old counters and none of these."""
    rec = trace.Recorder()
    rec.count("prefill_positions_fed", 40)
    with rec.span("tick", 1, "sched#0"):
        pass
    monkeypatch.setattr(trace, "_RECORDER", rec)
    logged = []
    monkeypatch.setattr(harness, "log", lambda **fields: logged.append(fields))
    assert _reader(name).read(_ctx()) is None
    assert logged == []


def test_every_cell_reports_the_six_that_move_setup_s():
    with open(os.path.join(harness.REPO_ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    under_setup = [m["name"] for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert [name for name in under_setup if name in METRICS] == list(METRICS)
    for cell in (w["name"] for w in manifest["workloads"]):
        per_layer = [m["name"] for m in harness.Cell(harness.REPO_ROOT, manifest, cell).per_layer]
        assert set(METRICS) <= set(per_layer)


@pytest.mark.parametrize("cell", ["t-chat", "t-train"])
def test_a_rehearsed_cell_reports_all_six_under_its_setup_s(traced, cell):
    line, logged, rec = traced[cell]
    got = {name: line["metrics"][name] for name in METRICS}       # all six are on the last line
    for name, (unit, _) in METRICS.items():
        assert got[name]["unit"] == unit and got[name]["value"] >= 0
    (setup,) = [entry for entry in logged if "setup_s" in entry]
    parts = sum(got[name]["value"] for name in SECONDS)
    assert 0 < parts <= setup["setup_s"], (got, setup)
    assert got["setup_trace_lower_s"]["value"] > 0 and got["setup_backend_load_s"]["value"] > 0
    assert got["setup_engine_init_s"]["value"] > 0
    assert 0 <= got["setup_cache_misses"]["value"] <= got["setup_programs_loaded"]["value"]
    # what compiled under no span of the program is reported, and is in none of the six
    (split,) = [entry["program_setup_split"] for entry in logged if "program_setup_split" in entry]
    assert split["compile_outside_s"] == rec.counters["compile_outside_us"] / 1e6 > 0
    in_spans = sum(root["trace_lower_s"] + root["backend_s"] for root in split["roots"].values())
    assert in_spans == pytest.approx(got["setup_trace_lower_s"]["value"]
                                     + got["setup_backend_load_s"]["value"])
    # no compile in the window: the program's count agrees with the runner's
    assert split["recompiles_in_units"] == len(split["recompiles"])
    window = [r for r in split["recompiles"] if r["at_s"] > setup["setup_s"]]
    assert len(window) == line["metrics"]["recompiles_in_window_chat" if cell == "t-chat"
                                          else "recompiles_in_window"]["value"] == 0


def test_the_split_divides_a_serving_start_by_entry_point(traced):
    _, logged, rec = traced["t-chat"]
    (split,) = [entry["program_setup_split"] for entry in logged if "program_setup_split" in entry]
    assert {"init_inference", "scheduler_init", "warmup"} <= set(split["roots"])
    for root in ("init_inference", "scheduler_init", "warmup"):
        r = split["roots"][root]
        assert r["trace_lower_s"] + r["backend_s"] <= r["span_s"]     # no sum exceeds its span
        assert r["cache_misses"] == r["programs"] - r["cache_hits"] >= 0
    # warm-up builds the whole-batch prefill, one program a rung below it, and decode
    assert split["roots"]["warmup"]["programs"] == 3
    assert {"prefill", "prefill_rung", "decode"} <= set(split["programs"])
    kinds = [r.kind for r in rec.records() if r.name == "program" and r.path == ("warmup",)]
    assert kinds == ["prefill", "prefill_rung", "decode"]
    by_name = {r.name: r for r in rec.records() if r.path == ("scheduler_init",)}
    assert {"cache_alloc", "serve_programs", "probe"} <= set(by_name)
    # the probe is the decode program's trace: warm-up's decode call finds it traced.
    # (On a loaded host a small function inside it may pass the ring's 5 ms too.)
    probe = [r for r in rec.records() if r.path == ("scheduler_init", "probe")
             and r.name == "compile_trace"]
    assert max(probe, key=lambda r: r.dur).kind == "decode"
    assert not [r for r in rec.records() if r.path == ("warmup", "program")
                and r.name == "compile_trace" and r.kind == "decode"]


def test_the_split_divides_a_training_start_by_entry_point(traced):
    _, logged, rec = traced["t-train"]
    (split,) = [entry["program_setup_split"] for entry in logged if "program_setup_split" in entry]
    assert {"initialize", "initialize_state", "train_batch"} <= set(split["roots"])
    # the first step is a root by its compiles alone: no span of the start lies around it
    assert split["roots"]["train_batch"]["span_s"] is None
    assert split["roots"]["initialize_state"]["span_s"] > 0
    children = {r.name for r in rec.records() if r.path == ("initialize_state",)}
    assert {"plan", "state_init", "build_step"} <= children
    # the first step's compile falls under that step's dispatch
    step = [r for r in rec.records() if r.name == "compile_backend" and "train_step" in r.kind]
    assert [(r.path, r.uid) for r in step] == [(("train_batch", "dispatch"), 1)]
    assert "train_step" in split["programs"]


def test_one_more_rung_is_exactly_one_more_program(bench_copy, traced, monkeypatch):
    """``traced`` has run the cell once in this process, so the small
    programs JAX keeps by shape are made: two more runs differ by the rung."""
    from deepspeed_tpu.inference.serving import scheduler

    root, manifest = bench_copy
    loaded = {}
    for rungs in ((1, 4), (1, 2, 4)):
        monkeypatch.setattr(scheduler, "prefill_rungs", lambda *a, rungs=rungs, **k: rungs)
        line, _, rec = _rehearse(root, manifest, "t-chat")
        loaded[rungs] = line["metrics"]["setup_programs_loaded"]["value"]
        assert rec.counters["setup_programs_loaded_warmup"] == len(rungs) + 1
    assert loaded[(1, 2, 4)] - loaded[(1, 4)] == 1


def test_the_totals_do_not_depend_on_what_the_ring_still_holds(monkeypatch):
    rec = trace.Recorder(capacity=4)
    monkeypatch.setattr(trace, "_RECORDER", rec)
    with rec.span("warmup", marks=trace.TOTAL):
        time.sleep(0.025)
        rec._on_compile_duration("/jax/core/compile/jaxpr_trace_duration", 0.025, fun_name="decode")
        time.sleep(0.05)     # one after the other, as JAX reports a program's phases
        rec._on_compile_duration("/jax/core/compile/backend_compile_duration", 0.05,
                                 fun_name="jit(decode)")
    with rec.span("scheduler_init", marks=trace.TOTAL):
        pass
    rec.count("setup_span_us_scheduler_init", 2_000_000)
    for i in range(8):
        with rec.span("tick", i):
            pass
    assert not [r for r in rec.records() if r.name.startswith("compile_")]
    logged = []
    monkeypatch.setattr(harness, "log", lambda **fields: logged.append(fields))
    ctx = _ctx()
    got = {name: program_setup.read(ctx, name) for name in METRICS}
    assert got["setup_trace_lower_s"] == pytest.approx(0.025, abs=1e-4)
    assert got["setup_backend_load_s"] == pytest.approx(0.05, abs=1e-4)
    assert (got["setup_programs_loaded"], got["setup_cache_misses"]) == (1, 1)
    assert got["setup_engine_init_s"] == pytest.approx(2.0, abs=1e-3)
    assert got["setup_import_s"] == 0
    assert len(logged) == 1 and "program_setup_split" in logged[0]     # once a run, by the first reader
