"""The reduction from a profiler trace to busy time, self time by
operation and idle gaps by span: exact on made-up intervals, and the
loader on a small trace recorded on a TPU v5e (benchmarks/testdata)."""

import os

import pytest

from benchmarks.lib import trace

from benchmarks.lib.harness import REPO_ROOT as REPO

RECORDED = os.path.join(REPO, "benchmarks", "testdata", "small_trace")


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_busy_seconds_counts_overlap_once():
    events = [("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0)]
    assert trace.busy_seconds(events) == pytest.approx(4.0)


def test_clip_cuts_to_the_window():
    events = [("a", 0.0, 2.0), ("b", 3.0, 2.0), ("c", 9.0, 1.0)]
    assert trace.clip(events, 1.0, 4.0) == [("a", 1.0, 1.0), ("b", 3.0, 1.0)]


def test_self_time_gives_nested_time_to_the_innermost():
    # a while of 10 s holding two bodies of 3 s, one of which holds 1 s
    events = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 3.0), ("copy.2", 2.0, 1.0),
              ("fusion.2", 5.0, 3.0)]
    times = trace.self_times(events)
    assert times == pytest.approx({"while.1": 4.0, "fusion.1": 2.0, "copy.2": 1.0, "fusion.2": 3.0})
    assert trace.by_family(times, {}) == pytest.approx({"while": 4.0, "fusion": 5.0, "copy": 1.0})


@pytest.mark.parametrize("name,family", [("fusion.123", "fusion"), ("copy", "copy"),
                                         ("all-gather.7", "all-gather"),
                                         ("convolution_add_fusion.12", "convolution_add_fusion")])
def test_op_family_drops_the_number(name, family):
    assert trace.op_family(name) == family


def test_gaps_go_to_the_span_the_host_was_in():
    busy = [(1.0, 2.0), (4.0, 5.0)]
    spans = [("window", 0.0, 6.0), ("train_batch", 0.0, 2.5), ("read_loss", 3.0, 1.0)]
    gaps = trace.gaps_by_span(busy, spans, 0.0, 6.0)
    # idle: [0,1] and [2,2.5] under train_batch, [2.5,3] under nothing,
    # [3,4] under read_loss, [5,6] under nothing
    assert gaps == pytest.approx({"train_batch": 1.5, "read_loss": 1.0,
                                  trace.NO_SPAN: 1.5})


def test_reduce_of_made_up_planes():
    loaded = {"devices": {"/device:TPU:0": [("fusion.1", 1.0, 1.0), ("all-gather.1", 3.0, 1.0)],
                          "/device:TPU:1": [("fusion.1", 1.0, 3.0)]},
              "spans": [("window", 0.0, 4.0), ("train_batch", 0.0, 4.0)],
              "op_stats": {}}
    r = trace.reduce(loaded)
    assert r["window_s"] == 4.0
    assert r["busy_s"] == pytest.approx(2.5) and r["busy_s_first"] == pytest.approx(2.0)
    assert r["family_seconds"] == pytest.approx({"fusion": 1.0, "all-gather": 1.0})
    assert r["idle_gaps"] == pytest.approx({"train_batch": 2.0})


def test_reduce_without_device_events_is_none():
    assert trace.reduce({"devices": {}, "spans": [("window", 0.0, 1.0)], "op_stats": {}}) is None
    assert trace.reduce({"devices": {"/device:TPU:0": [("a", 5.0, 1.0)]},
                         "spans": [("window", 0.0, 1.0)], "op_stats": {}}) is None


def test_top_orders_and_cuts():
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0], ["c", 2.0]]


# ---------------------------------------------------------------------------
# the recorded trace: three calls of a small jitted program holding one
# flash-attention kernel, on one TPU v5e chip (my chip run, PR 23), under
# bench:window with a bench:train_batch span around each call
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    return trace.load(trace.find_xplane(RECORDED))


def test_loader_finds_the_device_ops_and_the_spans(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    events = recorded["devices"]["/device:TPU:0"]
    assert len(events) == 9                       # copy, kernel, fusion: three times
    assert [s[0] for s in recorded["spans"]].count("train_batch") == 3
    assert [s[0] for s in recorded["spans"]].count(trace.WINDOW_SPAN) == 1
    assert all(dur > 0 for _, _, dur in events)


def test_hlo_text_names(recorded):
    texts = [name for name, _, _ in recorded["devices"]["/device:TPU:0"]]
    assert [trace.op_family(t) for t in texts[:3]] == ["copy", "_lambda_", "fusion"]
    assert [trace.is_custom_call(t) for t in texts[:3]] == [False, True, False]


def test_recorded_trace_reduces_to_consistent_numbers(recorded):
    from benchmarks.families import gpt2 as family
    r = trace.reduce(recorded, family.op_label)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(r["busy_s_first"])
    assert sum(r["family_seconds"].values()) == pytest.approx(r["busy_s"])
    # the kernel ran 2,822.5 ns each of three times (device_duration_ps in the trace)
    assert r["family_seconds"]["pallas:attn"] == pytest.approx(3 * 2822.5e-9, rel=1e-3)
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_gaps"].values()) == pytest.approx(idle)
    assert set(r["idle_gaps"]) == {"train_batch", trace.NO_SPAN}
