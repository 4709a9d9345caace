"""The per-layer metrics of the host's side of a tick (ISSUE 53): ``dispatch``
by part, whether the jitted call computes or waits, what it is handed, the
device's state under it as the program saw it, and stalled ticks and steps.
Each has its entry and its reader; each reader is held to a recorder filled by
hand, finds nothing where the program has no such span or counter (the parent),
and gives a number in the CPU rehearsal of every cell it lists. Nothing here is
a measurement: the platform is the CPU."""

import json
import os

import pytest

from benchmarks.lib import harness, program_dispatch, program_spans
from deepspeed_tpu.utils import trace

SEED = 2 ** 31 + 53
CHAT = ["serve-gpt2-medium-chat"]
SAT = ["serve-gpt2-medium-docs-sat", "serve-olmoe-1b-7b-agent-sat",
       "serve-nemotron-3-super-reason-sat", "serve-joyai-llm-flash-longdoc-sat",
       "serve-dots3-note-prev-longctx-sat", "serve-laguna-xs2-mixedlen-sat",
       "serve-ouro-2.6b-mathword-sat"]
TRAIN = ["train-gpt2-medium-seq1k", "train-gpt2-xl-zero3-x4", "train-smallthinker-21b-a3b-seq16k"]

# metric -> (unit, source, layer, moves, the cells its entry lists, the rehearsal's cells)
METRICS = {}
for _base, _unit, _source, _layer in [
        ("dispatch_launch_ms_p50", "ms", "program_span", "serving scheduler"),
        ("program_operand_leaves", "count", "program_counter", "serving programs"),
        ("device_dry_pct", "%", "program_span", "serving scheduler"),
        ("ticks_stalled", "count", "program_counter", "serving scheduler")]:
    METRICS[_base + "_chat"] = (_unit, _source, _layer, "itl_p95_ms", CHAT, ["t-chat"])
    METRICS[_base + "_sat"] = (_unit, _source, _layer, "serve_total_tok_s", SAT, ["t-docs"])
METRICS["steps_stalled_train"] = ("count", "program_counter", "training engine", "train_tok_s_chip",
                                  TRAIN, ["t-train", "t-train-x4"])
CTX = {"cell": None, "counters": {}, "spans": {}, "trace": None, "peaks": None}


def _read(name, root=harness.REPO_ROOT):
    return harness.load_module(root, harness.BENCH_DIR, "layer_metrics", name + ".py").read(CTX)


def test_the_nine_entries_were_appended_together_in_this_order():
    """Behind the 89 the manifest had; a later PR appends behind them. The issue's
    ``launch_blocked_pct_*`` are not among them: no per-layer metric reads a thread CPU clock
    that the benchmark's host steps by 10 ms (the split line has the ratio as read)."""
    names = [m["name"] for m in harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")["per_layer"]]
    assert names[89:98] == list(METRICS)
    assert not [n for n in names if n.startswith("launch_blocked")]


@pytest.mark.parametrize("name", list(METRICS))
def test_metric_has_its_entry_and_its_reader(name):
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    unit, source, layer, moves, cells, _ = METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": moves, "workloads": entry["workloads"]}
    assert entry["workloads"][:len(cells)] == cells      # a later PR's cell joins behind them
    reader = harness.load_module(harness.REPO_ROOT, harness.BENCH_DIR, "layer_metrics", name + ".py")
    assert callable(reader.read) and len(reader.__doc__) > 80     # says what it reads
    assert "None" in reader.__doc__                               # and what it does on the parent


@pytest.mark.parametrize("name", list(METRICS))
def test_reader_finds_nothing_in_an_empty_ring(name, monkeypatch):
    monkeypatch.setattr(trace, "_RECORDER", trace.Recorder())
    assert _read(name) is None


def test_readers_do_not_raise_where_the_program_has_no_recorder(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.utils.trace", None)   # import raises ImportError
    import deepspeed_tpu.utils
    monkeypatch.delattr(deepspeed_tpu.utils, "trace", raising=False)
    for name in METRICS:
        assert _read(name) is None


# -- a recorder filled by hand ---------------------------------------------------------------

class _Hand:
    """Ticks written into a recorder as the scheduler writes them, on made-up times."""

    def __init__(self, rec, parts=True):
        self.rec, self.t, self.parts, self.uid = rec, 10.0, parts, 0
        for uid in range(3):        # set-up's two requests, and the first of the pre-roll
            rec.record("queue_wait", 0.0, float(uid > 1), uid, "s")
        rec.record("queue_wait", 0.0, 2.0, 3, "s")

    def _span(self, name, seconds, path, kind=None):
        start, self.t = self.t, self.t + seconds
        self.rec._append(name, start, self.t, path, self.uid, "s", kind)
        return start

    def tick(self, kind="decode", launch_ms=1.0, account_ms=0.2, rest_ms=0.1, wait_ms=2.0,
             dry=None, gap=0.0, inflight=True):
        """One tick: admit 0.1 ms, build 0.3, stamp 0.05, dispatch (rest, launch, account), the
        read-back, commit 0.2, heartbeat 0.01, 0.04 ms between them; ``dry`` is the phase a
        ``device_dry`` record names."""
        self.uid += 1
        self.t += gap
        start = self.t
        if kind == "idle":
            self._span("admit", 1e-4, ("tick",))
            self._span("heartbeat", 1e-5, ("tick",))
            self.rec._append("tick", start, self.t, (), self.uid, "s", "idle")
            return
        self._span("admit", 1e-4, ("tick",))
        self.t += 4e-5                                        # the tick's own
        build = self._span("build_inputs", 3e-4, ("tick",))
        self._span("stamp", 5e-5, ("tick",))
        dispatch = self.t
        self.t += rest_ms / 1e3
        if self.parts:
            self._span("launch", launch_ms / 1e3, ("tick", "dispatch"), kind)
            launched = self.t
            self._span("account", account_ms / 1e3, ("tick", "dispatch"))
        else:
            self.t += (launch_ms + account_ms) / 1e3
            launched = self.t
        self.rec._append("dispatch", dispatch, self.t, ("tick",), self.uid, "s", None)
        if inflight:
            self._span("device_wait", wait_ms / 1e3, ("tick",))
        self._span("commit", 2e-4, ("tick",))
        self._span("heartbeat", 1e-5, ("tick",))
        self.rec._append("tick", start, self.t, (), self.uid, "s", kind)
        if dry is not None:
            seen = {"admit": build, "build_inputs": dispatch, "launch": launched}[dry]
            self.rec._append("device_dry", seen, launched, (), self.uid, "s", dry)


@pytest.fixture
def hand(monkeypatch):
    rec = trace.Recorder()
    monkeypatch.setattr(trace, "_RECORDER", rec)
    logged = []
    monkeypatch.setattr(harness, "log", lambda **fields: logged.append(fields))
    return _Hand(rec), rec, logged


def _fill(h, rec):
    """Ten fed decode ticks, one dry from its build, one dry from its dispatch, one under its
    launch, one into an empty scheduler after two idle ticks, four prefill ticks; then the
    profiler's start and a tick after it, which no reader may see."""
    for _ in range(10):
        h.tick()
    h.tick(launch_ms=3.0, dry="admit")
    h.tick(launch_ms=2.0, dry="build_inputs")
    h.tick(launch_ms=4.0, dry="launch")
    h.tick("idle")
    h.tick("idle")
    h.tick("prefill", launch_ms=6.0, inflight=False)
    for _ in range(4):
        h.tick("prefill", launch_ms=1.5, account_ms=0.4, wait_ms=20.0)
    h.tick(launch_ms=500.0, gap=program_spans.STALL_S + 0.1)
    rec.counters.update({
        "ticks_dispatched": 21, "ticks_dispatched_ahead": 18, "ticks_device_dry": 3,
        "ticks_device_dry_in_admit": 1, "ticks_device_dry_in_build_inputs": 1,
        "ticks_device_dry_in_launch": 1, "device_dry_us_min": 5450, "device_dry_us_max": 9940,
        "span_wall_us_launch": 40000, "span_cpu_us_launch": 10000,
        "span_wall_us_launch_decode": 28000, "span_cpu_us_launch_decode": 4000,
        "span_wall_us_launch_prefill": 12000, "span_cpu_us_launch_prefill": 6000,
        "program_operand_leaves": 430, "program_operand_bytes": 2 ** 30,
        "program_host_operands_decode": 1, "program_host_operands_prefill": 3,
        "units_stalled_tick": 0})


def test_the_readers_on_a_recorder_filled_by_hand(hand):
    h, rec, logged = hand
    _fill(h, rec)
    # eighteen launches: 10 x 1.0, 3.0, 2.0, 4.0, 6.0 and 4 x 1.5 ms
    assert _read("dispatch_launch_ms_p50_chat") == _read("dispatch_launch_ms_p50_sat") \
        == pytest.approx(1.0)
    assert _read("program_operand_leaves_chat") == _read("program_operand_leaves_sat") == 430
    assert _read("ticks_stalled_chat") == _read("ticks_stalled_sat") == 0
    assert _read("steps_stalled_train") is None         # no training step closed here
    # the lower bound: from the look that saw the program ended to the launch's return
    dry_s = (0.3 + 0.05 + 0.1 + 3.0) / 1e3 + (0.1 + 2.0) / 1e3 + 0.0
    ticks_s = sum(r.dur for r in rec.records() if r.name == "tick" and r.kind != "idle"
                  and r.uid <= 20)
    assert _read("device_dry_pct_chat") == _read("device_dry_pct_sat") \
        == pytest.approx(100.0 * dry_s / ticks_s)
    assert 0 < _read("device_dry_pct_sat") < 100


def test_the_split_line_by_part_by_device_state_and_what_the_ring_holds(hand):
    h, rec, logged = hand
    _fill(h, rec)
    rec._append("stall", 10.5, 11.5, (), 7, "s", "decode:device_wait")
    rec.counters.update(units_stalled_tick=1, stall_us_tick=10 ** 6)
    _read("dispatch_launch_ms_p50_chat")
    (split,) = [f["program_dispatch_split"] for f in logged if "program_dispatch_split" in f]
    assert set(split) == {"by_kind", "launch_wall_us", "launch_cpu_us", "launch_cpu_pct",
                          "launch_by_program", "launch_ms_by_device_state", "operand_leaves",
                          "operand_bytes", "host_operands", "launch_us_per_leaf", "device_dry",
                          "units_stalled", "stalls", "ring"}
    decode, prefill = split["by_kind"]["decode"], split["by_kind"]["prefill"]
    assert (decode["ticks"], decode["dispatched"], prefill["ticks"]) == (13, 13, 5)
    for kind in (decode, prefill):      # launch, account and the rest make up ``dispatch``
        assert {"dispatch", "launch", "account", "rest", "tick_self"} <= set(kind)
        assert kind["launch"]["sum_s"] + kind["account"]["sum_s"] + kind["rest"]["sum_s"] == \
            pytest.approx(kind["dispatch"]["sum_s"])
    assert decode["launch"]["p50_ms"] == pytest.approx(1.0)
    assert decode["account"]["p50_ms"] == pytest.approx(0.2)
    assert decode["rest"]["p50_ms"] == pytest.approx(0.1)
    assert decode["dispatch"]["p50_ms"] == pytest.approx(1.3)
    assert prefill["account"]["p50_ms"] == pytest.approx(0.4)
    assert decode["tick_self"]["p50_ms"] == pytest.approx(0.04)      # what no span names
    assert split["launch_cpu_pct"] == pytest.approx(25.0)
    assert split["launch_by_program"] == {"decode": {"wall_us": 28000, "cpu_us": 4000},
                                          "prefill": {"wall_us": 12000, "cpu_us": 6000}}
    states = split["launch_ms_by_device_state"]
    assert {s: v["ticks"] for s, v in states.items()} == \
        {"ended_after": 14, "ended_before": 2, "ended_during": 1, "nothing_in_flight": 1}
    assert states["ended_before"]["p50_ms"] == pytest.approx(2.5)
    assert states["ended_during"]["p50_ms"] == pytest.approx(4.0)
    assert states["nothing_in_flight"]["p50_ms"] == pytest.approx(6.0)
    assert split["operand_leaves"] == 430 and split["operand_bytes"] == 2 ** 30
    assert split["host_operands"] == {"decode": 1, "prefill": 3}
    assert split["launch_us_per_leaf"] == pytest.approx(1000.0 / 430)
    steady = split["device_dry"]["steady"]
    assert (steady["ticks"], steady["ticks_dry"]) == (18, 3)
    # the upper bound: from the look before. The tick's start, the build's, the dispatch's
    most = (0.1 + 0.04 + 0.3 + 0.05 + 0.1 + 3.0) / 1e3 + (0.3 + 0.05 + 0.1 + 2.0) / 1e3 + (0.1 + 4.0) / 1e3
    assert steady["dry_s_max"] == pytest.approx(most)
    assert 0 < steady["pct_min"] < steady["pct_max"] < 100
    assert split["device_dry"]["process"]["ticks_device_dry_in_launch"] == 1
    assert split["device_dry"]["process"]["ticks_dispatched_ahead"] == 18
    assert split["units_stalled"] == {"units_stalled_tick": 1, "stall_us_tick": 10 ** 6}
    assert split["stalls"] == [{"unit": 7, "source": "s", "kind": "decode:device_wait",
                                "at_s": pytest.approx(10.5), "ms": pytest.approx(1000.0)}]
    ring = split["ring"]
    assert ring["dropped"] == 0 and ring["written"] == ring["held"] == rec.last_seq
    assert ring["RING_RECORDS"] == trace.RING_RECORDS and ring["oldest_age_s"] > 0
    # a CPU clock that reads over the wall clock is printed as it was read, not cut at 100
    rec.counters["span_cpu_us_launch"] = 52000
    logged.clear()
    _read("dispatch_launch_ms_p50_sat")
    assert logged[-1]["program_dispatch_split"]["launch_cpu_pct"] == pytest.approx(130.0)


def test_dropped_is_on_the_line_when_the_ring_has_turned_over(monkeypatch):
    rec = trace.Recorder(capacity=150)
    monkeypatch.setattr(trace, "_RECORDER", rec)
    logged = []
    monkeypatch.setattr(harness, "log", lambda **fields: logged.append(fields))
    h = _Hand(rec)
    for _ in range(40):
        h.tick()
    assert _read("dispatch_launch_ms_p50_sat") is None      # set-up's requests went with the turn
    rec2 = trace.Recorder(capacity=400)
    monkeypatch.setattr(trace, "_RECORDER", rec2)
    h = _Hand(rec2)
    for _ in range(15):
        h.tick()
    for _ in range(200):
        h.tick("idle")
    for uid in range(4, 8):     # requests go on arriving: the oldest the ring holds open the sample
        rec2.record("queue_wait", 0.0, float(uid), uid, "s")
    for _ in range(10):
        h.tick()
    assert _read("dispatch_launch_ms_p50_sat") == pytest.approx(1.0)
    ring = logged[-1]["program_dispatch_split"]["ring"]
    assert ring["dropped"] == rec2.dropped > 0 and ring["held"] == 400
    assert ring["written"] == ring["held"] + ring["dropped"]


def test_a_program_with_the_old_dispatch_span_alone_reads_as_nothing(hand):
    """The parent: ticks with a ``dispatch`` and no ``launch`` under it, none of the counters."""
    h, rec, logged = hand
    h.parts = False
    for _ in range(12):
        h.tick()
    rec.counters.update(ticks_dispatched=12, ticks_dispatched_ahead=11)
    for name in METRICS:
        assert _read(name) is None
    assert logged == []


def test_a_stalled_step_is_read_with_its_record(hand):
    h, rec, logged = hand
    rec._append("train_batch", 1.0, 4.0, (), 9, "engine#0", None)
    rec._append("stall", 1.0, 4.0, (), 9, "engine#0", "train_batch:timer_sync")
    rec.counters.update(units_stalled_train_batch=1, stall_us_train_batch=3 * 10 ** 6)
    assert _read("steps_stalled_train") == 1
    (line,) = [f["program_stalls"] for f in logged]
    assert line["units_stalled"] == 1 and line["stall_us"] == 3 * 10 ** 6
    assert [s["kind"] for s in line["stalls"]] == ["train_batch:timer_sync"]
    assert line["ring"]["dropped"] == 0


# -- the CPU rehearsal of every cell an entry lists ------------------------------------------------

@pytest.fixture(scope="module")
def traced(bench_copy):
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
def test_reader_gives_a_number_in_the_rehearsal_of_each_cell_it_lists(traced, name):
    for cell in METRICS[name][5]:
        line = traced[cell][0]
        assert line["correct"] is True and line["failed"] == 0
        metric = line["metrics"][name]
        assert metric["unit"] == METRICS[name][0] and metric["value"] >= 0
        if "stalled" in name:
            assert metric["value"] == 0          # no steady rehearsal counts a stall
        if name.endswith(("_pct_chat", "_pct_sat")):
            assert metric["value"] < 100


@pytest.mark.parametrize("cell", ["t-chat", "t-docs"])
def test_the_rehearsals_split_adds_up_and_agrees_with_the_tick_split(traced, cell):
    line, logged, rec = traced[cell]
    (split,) = [f["program_dispatch_split"] for f in logged if "program_dispatch_split" in f]
    (ticks,) = [f["program_tick_split"] for f in logged if "program_tick_split" in f]
    assert set(split["by_kind"]) == set(ticks) >= {"prefill", "decode"}
    for kind, parts in split["by_kind"].items():
        assert parts["ticks"] == ticks[kind]["ticks"]        # the same steady ticks
        assert parts["dispatch"]["sum_s"] == pytest.approx(ticks[kind]["phases"]["dispatch"]["sum_s"])
        assert parts["launch"]["sum_s"] + parts["account"]["sum_s"] + parts["rest"]["sum_s"] == \
            pytest.approx(parts["dispatch"]["sum_s"])
        assert parts["rest"]["sum_s"] >= 0 and parts["tick_self"]["sum_s"] >= 0
    assert 0 <= split["launch_cpu_us"] <= split["launch_wall_us"]
    leaves = line["metrics"]["program_operand_leaves_" + ("chat" if cell == "t-chat" else "sat")]
    assert split["operand_leaves"] == leaves["value"] > 10 and split["operand_bytes"] > 0
    assert split["host_operands"]["decode"] == 1 and split["host_operands"]["prefill"] == 3
    steady = split["device_dry"]["steady"]
    assert 0 <= steady["dry_s_min"] <= steady["dry_s_max"] <= steady["ticks_s"]
    process = split["device_dry"]["process"]
    assert process["ticks_device_dry"] <= process["ticks_dispatched_ahead"] \
        <= process["ticks_dispatched"]
    assert process.get("device_dry_us_min", 0) <= process.get("device_dry_us_max", 0)
    assert sum(v["ticks"] for v in split["launch_ms_by_device_state"].values()) == \
        sum(parts["dispatched"] for parts in split["by_kind"].values())
    assert split["stalls"] == [] and split["units_stalled"] == {"units_stalled_tick": 0}
    assert split["ring"]["dropped"] == rec.dropped == 0
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace, "_RECORDER", rec)
        chosen = program_dispatch.steady_ticks(rec.records())
        assert len([t for t, _ in chosen if t.kind != "idle"]) == \
            len(program_spans.serving()["ticks"])


def test_a_training_rehearsal_prints_its_stalls_and_the_rings_state(traced):
    for cell in ("t-train", "t-train-x4"):
        _, logged, rec = traced[cell]
        (line,) = [f["program_stalls"] for f in logged if "program_stalls" in f]
        assert line["units_stalled"] == 0 and line["stalls"] == []
        assert line["ring"]["written"] == rec.last_seq and line["ring"]["dropped"] == 0
