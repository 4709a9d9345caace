"""The program's one recorder (``deepspeed_tpu/utils/trace.py``): nesting,
the shared identifier, the bounded ring, counters, what it costs, and that
its spans reach a profiler session under ``ds:``."""

import glob
import os
import threading
import time

from deepspeed_tpu.utils import trace


def test_nesting_gives_every_span_its_parent_and_path():
    rec = trace.Recorder()
    with rec.span("tick", uid=4, source="sched#0") as tick:
        with rec.span("admit", 4, "sched#0"):
            pass
        with rec.span("commit", 4, "sched#0"):
            with rec.span("publish", 4, "sched#0"):
                pass
        tick.kind = "decode"       # known only once the tick has chosen its work
    by_name = {r.name: r for r in rec.records()}
    assert [r.name for r in rec.records()] == ["admit", "publish", "commit", "tick"]  # closing order
    assert by_name["tick"].parent is None and by_name["tick"].path == ()
    assert by_name["admit"].parent == "tick"
    assert by_name["publish"].parent == "commit" and by_name["publish"].path == ("tick", "commit")
    assert by_name["tick"].kind == "decode" and by_name["admit"].kind is None
    tick = by_name["tick"]
    for child in ("admit", "commit", "publish"):
        assert tick.start <= by_name[child].start <= by_name[child].end <= tick.end
    assert rec.last_span == "publish"      # the last one entered: the heartbeat's breadcrumb


def test_spans_of_one_unit_of_work_share_an_identifier_and_a_source():
    rec = trace.Recorder()
    for step in (1, 2):
        with rec.span("train_batch", step, "engine#0"):
            with rec.span("dispatch", step, "engine#0"):
                pass
    with rec.span("tick", 1, "sched#1"):
        pass
    mine = rec.records("engine#0")
    assert [(r.name, r.uid) for r in mine] == [("dispatch", 1), ("train_batch", 1),
                                               ("dispatch", 2), ("train_batch", 2)]
    assert [r.name for r in rec.records("sched#1")] == ["tick"]
    assert trace.new_source("engine") != trace.new_source("engine")


def test_ring_is_bounded_and_counts_what_it_dropped():
    rec = trace.Recorder(capacity=3)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    assert [r.name for r in rec.records()] == ["s2", "s3", "s4"]
    assert [r.seq for r in rec.records()] == [3, 4, 5]
    assert rec.dropped == 2 and rec.last_seq == 5


def test_since_reads_on_from_a_cursor_and_says_what_was_lost():
    rec = trace.Recorder(capacity=4)
    with rec.span("a", source="x"):
        pass
    cursor = rec.last_seq
    for name, source in (("b", "x"), ("c", "y"), ("d", "x")):
        with rec.span(name, source=source):
            pass
    got, lost = rec.since(cursor, "x")
    assert [r.name for r in got] == ["b", "d"] and lost == 0
    for _ in range(4):      # the ring turns over past the cursor
        with rec.span("e", source="x"):
            pass
    got, lost = rec.since(cursor, "x")
    assert [r.name for r in got] == ["e"] * 4 and lost == 3
    assert rec.since(rec.last_seq) == ([], 0)


def test_counters_are_plain_integers():
    rec = trace.Recorder()
    rec.count("prefill_positions_fed", 36)
    rec.count("prefill_positions_fed", 4)
    rec.count("serve_program_builds")
    assert rec.counters == {"prefill_positions_fed": 40, "serve_program_builds": 1}


def test_an_empty_recorder_reads_as_nothing():
    rec = trace.Recorder()
    assert rec.records() == [] and rec.records("anyone") == []
    assert rec.since(0) == ([], 0)
    assert rec.counters == {} and rec.dropped == 0 and rec.last_span is None


def test_a_back_dated_interval_goes_to_the_ring_on_the_callers_clock():
    rec = trace.Recorder()
    rec.record("queue_wait", 10.0, 12.5, uid=7, source="sched#0")
    (r,) = rec.records()
    assert (r.name, r.start, r.end, r.uid, r.source, r.path) == \
        ("queue_wait", 10.0, 12.5, 7, "sched#0", ())
    assert r.dur == 2.5 and r.parent is None


def test_a_span_closes_and_is_recorded_when_its_body_raises():
    rec = trace.Recorder()
    try:
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("boom")
    except ValueError:
        pass
    assert [r.name for r in rec.records()] == ["inner", "outer"]
    with rec.span("next"):
        pass
    assert rec.records()[-1].path == ()      # the stack unwound


def test_each_thread_nests_on_its_own_stack():
    rec = trace.Recorder()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with rec.span("worker_outer"):
            inside.set()
            assert release.wait(timeout=10)

    thread = threading.Thread(target=worker)
    thread.start()
    assert inside.wait(timeout=10)
    with rec.span("main_span"):     # opened while the worker's span is open
        pass
    release.set()
    thread.join(timeout=10)
    assert not thread.is_alive()
    by_name = {r.name: r for r in rec.records()}
    assert by_name["main_span"].path == () and by_name["worker_outer"].path == ()


def test_ten_thousand_spans_cost_under_a_fifth_of_a_second():
    rec = trace.Recorder()
    best = float("inf")
    for _ in range(3):      # the least of three: a shared core can stall any one
        t0 = time.perf_counter()
        for i in range(10_000):
            with rec.span("phase", i, "sched#0"):
                pass
        best = min(best, time.perf_counter() - t0)
    assert best < 0.2, f"10,000 spans took {best:.3f} s"
    assert rec.last_seq == 30_000


def test_spans_lie_in_a_profiler_session_under_the_prefix(tmp_path):
    """In any profiler session the spans are host events named ``ds:<name>``,
    nested as the ring nests them; outside one they still reach the ring."""
    import jax
    from jax.profiler import ProfileData

    rec = trace.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("tick", 1):
            with rec.span("device_wait", 1):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    with rec.span("after_the_session"):
        pass
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(trace.PREFIX):
                        found[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    assert set(found) == {"ds:tick", "ds:device_wait"}
    assert found["ds:tick"][0] <= found["ds:device_wait"][0]
    assert found["ds:device_wait"][1] <= found["ds:tick"][1]
    assert found["ds:device_wait"][1] - found["ds:device_wait"][0] >= 2e6      # the sleep, in ns
    assert [r.name for r in rec.records()] == ["device_wait", "tick", "after_the_session"]
