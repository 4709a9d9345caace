"""The reduction from a profiler trace to busy time, self time by
operation and idle gaps by span: exact on made-up intervals, and the
loader on a small trace recorded on a TPU v5e (benchmarks/testdata)."""

import os
import random

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


def gaps_by_span_plain(busy, spans, lo, hi):
    """The plain form ``trace.gaps_by_span`` has to equal: every span held
    against every gap (751 s on a traced chat run's 834,282 busy intervals
    and 2,809 spans)."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    out = {}
    for g0, g1 in gaps:
        left = g1 - g0
        for name, start, dur in spans:
            if name == trace.WINDOW_SPAN:
                continue
            over = min(g1, start + dur) - max(g0, start)
            if over > 0:
                out[name] = out.get(name, 0.0) + over
                left -= over
        if left > 1e-12:
            out[trace.NO_SPAN] = out.get(trace.NO_SPAN, 0.0) + left
    return out


def made_up_trace(seed, n_busy=400, n_ticks=60):
    """Busy intervals on a microsecond grid (so that spans touch gaps and
    each other exactly) under ticks that hold children, which hold children
    of their own, with stretches under no span and spans of no length."""
    rng = random.Random(seed)
    us = 1e-6
    t, busy = 0, []
    for _ in range(n_busy):
        t += rng.choice((0, 1, 3, 40, 900))
        d = rng.choice((1, 2, 25, 300))
        busy.append((t * us, (t + d) * us))
        t += d
    hi = t + 50
    spans, at = [("window", 0.0, hi * us)], 0
    for k in range(n_ticks):
        at += rng.choice((0, 0, 7, 500))                        # touching the last tick, or apart
        dur = rng.choice((0, 200, 2000, 9000))
        spans.append((f"tick_{k % 3}", at * us, dur * us))
        c = at
        while dur and c < at + dur:                             # children end to end, the last one short
            cd = min(rng.choice((1, 50, 700)), at + dur - c)
            spans.append((rng.choice(("admit", "dispatch", "device_wait")), c * us, cd * us))
            if cd > 10 and rng.random() < 0.5:                  # a grandchild over the child's middle
                spans.append(("device_wait", (c + 2) * us, (cd - 4) * us))
            c += cd + rng.choice((0, 0, 5))
        at += dur
    return trace.union(busy), sorted(spans, key=lambda s: s[1]), 0.0, hi * us


def held_equal(got, want):
    assert set(got) == set(want)
    for name in want:
        assert got[name] == pytest.approx(want[name], abs=1e-9), name
    assert [k for k, _ in trace.top(got)] == [k for k, _ in trace.top(want)]


@pytest.mark.parametrize("seed", [44, 2 ** 31 + 44, 4400000111])
def test_the_walk_gives_what_every_span_against_every_gap_gives(seed):
    busy, spans, lo, hi = made_up_trace(seed)
    want = gaps_by_span_plain(busy, spans, lo, hi)
    assert {"tick_0", "device_wait", trace.NO_SPAN} <= set(want)     # nested, and under nothing
    # an outer span is met by every gap under it: more is given out than is idle
    idle = (hi - lo) - sum(e - s for s, e in busy)
    assert sum(want.values()) > idle
    held_equal(trace.gaps_by_span(busy, spans, lo, hi), want)
    # cut anywhere: a window that opens inside a busy interval and closes inside a gap
    lo2, hi2 = busy[3][0] + 0.4e-6, busy[-5][1] + 0.5e-6
    clipped = [(max(s, lo2), min(e, hi2)) for s, e in busy if min(e, hi2) > max(s, lo2)]
    held_equal(trace.gaps_by_span(clipped, spans, lo2, hi2),
               gaps_by_span_plain(clipped, spans, lo2, hi2))


@pytest.mark.parametrize("busy, spans", [
    ([], [("tick", 0.0, 1.0)]),                                      # one gap, the whole window
    ([(0.0, 1.0)], [("tick", 0.0, 1.0)]),                            # no gap at all
    ([(0.2, 0.4)], []),                                              # no span
    ([(0.2, 0.4), (0.6, 0.8)], [("a", 0.4, 0.2), ("b", 0.8, 0.0), ("c", 0.0, 0.2)]),   # spans that fit gaps exactly
    ([(0.2, 0.4), (0.6, 0.8)], [("a", 0.0, 1.0), ("a", 0.1, 0.8), ("b", 0.45, 0.1)]),  # one name, nested in itself
])
def test_the_walk_on_edges(busy, spans):
    held_equal(trace.gaps_by_span(busy, spans, 0.0, 1.0), gaps_by_span_plain(busy, spans, 0.0, 1.0))


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


def test_the_walk_on_the_recorded_trace(recorded):
    window, = [s for s in recorded["spans"] if s[0] == trace.WINDOW_SPAN]
    lo, hi = window[1], window[1] + window[2]
    first = trace.clip(recorded["devices"]["/device:TPU:0"], lo, hi)
    busy = trace.union((s, s + d) for _, s, d in first)
    want = gaps_by_span_plain(busy, recorded["spans"], lo, hi)
    assert want["train_batch"] > 0
    held_equal(trace.gaps_by_span(busy, recorded["spans"], lo, hi), want)
