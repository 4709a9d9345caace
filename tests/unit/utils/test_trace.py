"""The program's one recorder (``deepspeed_tpu/utils/trace.py``): nesting,
the shared identifier, the bounded ring, counters, what it costs, and that
its spans reach a profiler session under ``ds:``."""

import glob
import os
import threading
import time

import pytest

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


# ---------------------------------------------------------------------------
# JAX's compile events, filed under the span that caused them
# ---------------------------------------------------------------------------
@pytest.fixture
def hearing(monkeypatch):
    """A recorder of this test's own as the process's: the listeners, which
    the module registered once, reach whichever recorder that is."""
    rec = trace.Recorder()
    monkeypatch.setattr(trace, "_RECORDER", rec)
    return rec


def _fresh_jit(name):
    """A jitted function no other test has compiled, under a name to look for."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        time.sleep(0.006)    # runs while tracing only; a trace under 5 ms is counted, not recorded
        return jnp.tanh(x) * 3.0 + 1.0
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _compiles(rec, fun):
    return [r for r in rec.records() if r.name.startswith("compile_") and fun in (r.kind or "")]


def test_a_compile_is_filed_under_the_open_span_and_counted_under_its_root(hearing):
    import jax.numpy as jnp

    fn, x = _fresh_jit("filed_under_the_span"), jnp.ones((3, 5))
    hearing.counters.clear()     # making ``x`` compiled too, under no span
    with hearing.span("warmup", source="sched#7", marks=trace.TOTAL):
        with hearing.span("program", 11, "sched#7") as program:
            program.kind = "decode"
            fn(x)
    mine = _compiles(hearing, "filed_under_the_span")
    # the trace is named by the function, the rest by JAX's ``jit(...)``
    assert {r.name for r in mine} == {"compile_trace", "compile_lower", "compile_backend"}
    by_name = {r.name: r for r in mine}
    assert by_name["compile_trace"].kind == "filed_under_the_span"
    assert by_name["compile_backend"].kind == "jit(filed_under_the_span)"
    span = {r.name: r for r in hearing.records()}["program"]
    for r in mine:
        assert (r.path, r.uid, r.source) == (("warmup", "program"), 11, "sched#7")
        assert span.start <= r.start <= r.end <= span.end + 1e-3     # back-dated into the span
    assert (by_name["compile_trace"].end <= by_name["compile_lower"].end
            <= by_name["compile_backend"].end)
    c = hearing.counters
    assert c["setup_programs_loaded_warmup"] == 1 and c["setup_cache_hits_warmup"] in (0, 1)
    assert c["setup_trace_lower_us_warmup"] > 0 and c["setup_backend_us_warmup"] > 0
    # no total exceeds the span it fell under, and that span is a counter too
    assert (c["setup_trace_lower_us_warmup"] + c["setup_backend_us_warmup"]
            <= c["setup_span_us_warmup"])
    assert "compile_outside_us" not in c and "recompiles_in_units" not in c


def test_the_second_call_of_a_function_records_nothing(hearing):
    import jax.numpy as jnp

    fn, x = _fresh_jit("called_twice"), jnp.ones((2, 2))
    with hearing.span("warmup"):
        fn(x)
    seen, counters = hearing.last_seq, dict(hearing.counters)
    with hearing.span("warmup"):
        fn(x)
    assert [r.name for r in hearing.records() if r.seq > seen] == ["warmup"]
    assert hearing.counters == counters


def test_a_compile_under_no_span_goes_to_compile_outside(hearing):
    import jax.numpy as jnp

    fn, x = _fresh_jit("under_no_span"), jnp.ones((4,))
    hearing.counters.clear()
    fn(x)
    mine = _compiles(hearing, "under_no_span")
    assert len(mine) == 3 and all((r.path, r.uid, r.source) == ((), None, None) for r in mine)
    assert set(hearing.counters) == {"compile_outside_us"}
    assert hearing.counters["compile_outside_us"] <= sum(r.dur for r in mine) * 1e6 + 3


def test_a_function_traced_inside_another_is_counted_once(hearing):
    """The inner ``jit``'s trace event arrives first, inside the outer's
    interval: its microseconds are taken back when the outer's arrive."""
    import jax
    import jax.numpy as jnp

    inner = _fresh_jit("inner_of_two")

    def outer(x):
        time.sleep(0.01)     # so that the inner's own time could not hide in rounding
        return inner(x) + inner(x * 2.0)
    outer.__name__ = outer.__qualname__ = "outer_of_two"
    x = jnp.ones((6,))
    hearing.counters.clear()
    with hearing.span("warmup", marks=trace.TOTAL):
        jax.jit(outer)(x)
    traces = [r for r in hearing.records() if r.name == "compile_trace"]
    outer_trace = [r for r in traces if r.kind == "outer_of_two"]
    assert len(outer_trace) == 1 and outer_trace[0].dur >= 0.01
    lowered = sum(r.dur for r in hearing.records()
                  if r.name == "compile_lower" and r.path == ("warmup",))
    c = hearing.counters
    # trace + lowering is the outer's trace and the lowerings, not the inner traces again
    assert c["setup_trace_lower_us_warmup"] == pytest.approx(
        (outer_trace[0].dur + lowered) * 1e6, abs=200)
    assert (c["setup_trace_lower_us_warmup"] + c["setup_backend_us_warmup"]
            <= c["setup_span_us_warmup"])
    assert c["setup_programs_loaded_warmup"] == 1      # one program came of it


class _ToyScheduler:
    """The scheduler's shape as the recorder sees it: ticks of a kind, each
    with a ``dispatch`` phase that calls a jitted step."""

    def __init__(self, rec, name):
        self.rec, self.source, self.tick_no = rec, trace.new_source("toy"), 0
        self.step = _fresh_jit(name)

    def warmup(self, x):
        with self.rec.span("warmup", source=self.source, marks=trace.WARMS | trace.TOTAL):
            self.step(x)

    def tick(self, x, kind="decode", admit=None):
        self.tick_no += 1
        with self.rec.span("tick", self.tick_no, self.source, trace.UNIT) as tick:
            if admit is not None:       # before the tick has chosen its work
                with self.rec.span("admit", self.tick_no, self.source):
                    admit()
            tick.kind = kind
            with self.rec.span("dispatch", self.tick_no, self.source):
                return self.step(x)


def test_a_retrace_in_the_second_tick_is_a_recompile_that_names_it(hearing):
    import jax.numpy as jnp

    sched = _ToyScheduler(hearing, "toy_decode")
    same, other, prompt = jnp.ones((4, 8)), jnp.ones((4, 9)), jnp.ones((2, 3))
    sched.tick(same)            # nobody warmed it: the first tick of its kind compiles
    assert "recompiles_in_units" not in hearing.counters
    assert hearing.counters["setup_programs_loaded_tick"] == 1
    sched.tick(same)
    assert "recompiles_in_units" not in hearing.counters
    sched.tick(other)           # another shape: the same function compiles again
    c = hearing.counters
    assert c["recompiles_in_units"] == 1
    assert c["recompile_us"] > 0 and c["setup_programs_loaded_tick"] == 1
    (r,) = [r for r in hearing.records() if r.name == "recompile"]
    assert (r.uid, r.parent, r.kind, r.source) == (3, "dispatch", "jit(toy_decode)", sched.source)
    assert r.path == ("tick", "dispatch")
    tick = [t for t in hearing.records(sched.source) if t.name == "tick" and t.uid == r.uid]
    assert tick[0].kind == "decode" and tick[0].start <= r.start <= r.end <= tick[0].end + 1e-3
    # a tick of another kind has finished no unit yet: its first compile is set-up
    sched.tick(prompt, kind="prefill")
    assert c["recompiles_in_units"] == 1 and c["setup_programs_loaded_tick"] == 2


def test_the_first_tick_of_a_warmed_scheduler_may_compile_nothing(hearing):
    import jax.numpy as jnp

    sched = _ToyScheduler(hearing, "toy_warmed")
    warmed, missed = jnp.ones((4, 8)), jnp.ones((5, 8))
    sched.warmup(warmed)
    sched.tick(warmed)          # what warm-up compiled: nothing to record
    assert "recompiles_in_units" not in hearing.counters
    sched.tick(missed)          # what warm-up missed, in the first tick of its kind or not
    assert hearing.counters["recompiles_in_units"] == 1
    (r,) = [r for r in hearing.records() if r.name == "recompile"]
    assert (r.uid, r.parent) == (2, "dispatch")


def test_a_compile_before_a_tick_has_chosen_its_work_does_not_settle_the_tick(hearing):
    """The verdict is kept by the unit and its kind: what ``admit`` compiles
    in a tick of no kind yet says nothing of the ``dispatch`` that follows."""
    import jax.numpy as jnp

    sched = _ToyScheduler(hearing, "toy_admits")
    copy = _fresh_jit("toy_admit_copy")
    same, other, row = jnp.ones((4, 8)), jnp.ones((4, 9)), jnp.ones((3,))
    sched.tick(same)
    sched.tick(other, admit=lambda: copy(row))
    c = hearing.counters
    # nobody warmed it and no tick of no kind has finished: admit's compile is set-up
    assert c["setup_programs_loaded_tick"] == 2 and c["recompiles_in_units"] == 1
    (r,) = [r for r in hearing.records() if r.name == "recompile"]
    assert (r.uid, r.parent, r.kind) == (2, "dispatch", "jit(toy_admits)")


def test_a_unit_under_another_root_is_a_unit_and_a_name_alone_is_none(hearing):
    """The opener says what a unit is, not the span's name: a tick inside a
    rollout's span recompiles, a span that is only called ``tick`` does not."""
    import jax.numpy as jnp

    sched = _ToyScheduler(hearing, "toy_rollout")
    sched.warmup(jnp.ones((4, 8)))
    with hearing.span("rollout", 1, "rlhf#0"):
        sched.tick(jnp.ones((4, 9)))
    c = hearing.counters
    assert c["recompiles_in_units"] == 1 and "setup_programs_loaded_rollout" not in c
    (r,) = [r for r in hearing.records() if r.name == "recompile"]
    assert (r.path, r.uid, r.source) == (("rollout", "tick", "dispatch"), 1, sched.source)
    named = _fresh_jit("toy_named_tick")
    for no, x in enumerate([jnp.ones((2,)), jnp.ones((3,))], start=1):
        with hearing.span("tick", no, "other#0"):
            named(x)
    assert c["recompiles_in_units"] == 1 and c["setup_programs_loaded_tick"] == 2


def test_the_intervals_kept_for_nesting_are_bounded(hearing, monkeypatch):
    monkeypatch.setattr(trace, "_NEST_KEPT", 8)
    for _ in range(50):     # a process that retraces for ever, none inside another
        hearing._on_compile_duration("/jax/core/compile/jaxpr_trace_duration", 1e-5, fun_name="f")
        time.sleep(2e-4)
    assert len(hearing._local.nest) <= 8
    assert 450 <= hearing.counters["compile_outside_us"] <= 500     # every one counted, once


def test_the_first_step_of_an_engine_is_no_recompile_and_a_later_one_is(hearing):
    import jax.numpy as jnp

    step = _fresh_jit("toy_train_step")
    batches = [jnp.ones((2, 8)), jnp.ones((2, 8)), jnp.ones((2, 16))]
    for no, batch in enumerate(batches, start=1):
        with hearing.span("train_batch", no, "engine#9", trace.UNIT):
            with hearing.span("dispatch", no, "engine#9"):
                step(batch)
    c = hearing.counters
    assert c["setup_programs_loaded_train_batch"] == 1 and c["recompiles_in_units"] == 1
    (r,) = [r for r in hearing.records() if r.name == "recompile"]
    assert (r.uid, r.parent, r.kind) == (3, "dispatch", "jit(toy_train_step)")


def test_a_cache_hit_is_counted_for_the_compile_it_answers(hearing, tmp_path):
    """Counted from hits: the event precedes its backend compile's duration."""
    with hearing.span("warmup"):
        hearing._on_compile_event("/jax/compilation_cache/cache_hits")
        hearing._on_compile_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.004)
        hearing._on_compile_duration("/jax/core/compile/backend_compile_duration", 0.005,
                                     fun_name="jit(answered)")
        hearing._on_compile_duration("/jax/core/compile/backend_compile_duration", 0.002,
                                     fun_name="jit(compiled)")
        hearing._on_compile_duration("/jax/some/other/event", 1.0)
    c = hearing.counters
    assert c["setup_programs_loaded_warmup"] == 2 and c["setup_cache_hits_warmup"] == 1
    assert c["setup_cache_load_us_warmup"] == 4000
    assert 6900 <= c["setup_backend_us_warmup"] <= 7100     # the retrieval is inside the first
    assert [r.name for r in hearing.records()] == ["compile_cache_load", "compile_backend",
                                                   "compile_backend", "warmup"]


def test_listeners_are_registered_once_for_any_number_of_engines_and_schedulers():
    import jax.numpy as jnp
    from jax._src import monitoring

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ContinuousBatchingScheduler, ServingConfig
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    def mine(listeners):
        return [fn for fn in listeners if getattr(fn, "__module__", None) == trace.__name__]

    model = GPT2LMHeadModel(get_gpt2_config("test"))
    for _ in range(2):
        engine = deepspeed_tpu.init_inference(model, dtype=jnp.float32, max_out_tokens=32)
        ContinuousBatchingScheduler(engine, ServingConfig(slots=2, prefill_chunk=8))
        deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": 8, "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    assert len(mine(monitoring.get_event_duration_listeners())) == 1
    assert len(mine(monitoring.get_event_listeners())) == 1


def test_a_record_takes_the_open_span_and_keeps_what_it_is_given():
    rec = trace.Recorder()
    with rec.span("tick", 5, "sched#0"):
        with rec.span("admit", 5, "sched#0"):
            rec.record("queue_wait", 1.0, 2.0, uid=77, source="sched#0")   # a request's own uid
            rec.record("noted", 1.0, 2.0, kind="why")
    by_name = {r.name: r for r in rec.records()}
    assert by_name["queue_wait"].path == ("tick", "admit") and by_name["queue_wait"].uid == 77
    assert (by_name["noted"].uid, by_name["noted"].source, by_name["noted"].kind) == \
        (5, "sched#0", "why")


def test_a_set_up_span_leaves_its_seconds_in_a_counter_where_it_is_a_root():
    rec = trace.Recorder(capacity=2)
    with rec.span("scheduler_init", source="sched#0", marks=trace.TOTAL):
        with rec.span("warmup", marks=trace.TOTAL):      # not a root here: the span alone
            time.sleep(0.002)
    for i in range(4):                      # the ring turns over; the counter stays
        with rec.span("tick", i):
            pass
    assert "scheduler_init" not in [r.name for r in rec.records()]
    assert rec.counters["setup_span_us_scheduler_init"] >= 2000
    assert "setup_span_us_warmup" not in rec.counters


def test_an_import_inside_another_is_counted_once(hearing):
    t0 = time.perf_counter()
    time.sleep(0.003)
    inner0 = time.perf_counter()
    time.sleep(0.002)
    trace.imported("pkg.inner", inner0)
    trace.imported("pkg", t0)
    records = [r for r in hearing.records() if r.name == "import"]
    assert [r.kind for r in records] == ["pkg.inner", "pkg"]
    assert hearing.counters["setup_import_us"] == pytest.approx(records[1].dur * 1e6, abs=2)


def test_the_packages_record_their_own_import():
    import deepspeed_tpu  # noqa: F401
    import deepspeed_tpu.inference.serving  # noqa: F401

    kinds = {r.kind for r in trace.recorder().records() if r.name == "import"}
    # (the ring of a long test process may have turned over: the counter has not)
    assert trace.recorder().counters["setup_import_us"] > 0
    assert not kinds or kinds & {"deepspeed_tpu", "deepspeed_tpu.inference.serving"}


def test_a_lazy_export_of_a_module_already_imported_writes_no_record(hearing):
    import deepspeed_tpu
    import deepspeed_tpu.runtime.config  # noqa: F401

    deepspeed_tpu.__dict__.pop("DeepSpeedConfig", None)     # as before its first use
    assert deepspeed_tpu.DeepSpeedConfig is deepspeed_tpu.runtime.config.DeepSpeedConfig
    assert not [r for r in hearing.records() if r.name == "import"]
    assert "setup_import_us" not in hearing.counters


# -- the CPU mark, and the stall rule (ISSUE 53) ------------------------------------------------

def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("work, seconds, cpu_share", [(time.sleep, 0.05, (0.0, 0.3)),
                                                       (_spin, 0.03, (0.5, 1.0))],
                         ids=["a-sleep-waits", "a-spin-computes"])
def test_a_span_marked_cpu_reads_cpu_time_beside_wall_time(work, seconds, cpu_share):
    rec = trace.Recorder()
    with rec.span("launch", 1, "s", trace.CPU) as launch:
        launch.kind = "decode"
        work(seconds)
    c = rec.counters
    wall, cpu = c["span_wall_us_launch"], c["span_cpu_us_launch"]
    assert wall >= seconds * 1e6 * 0.9 and 0 <= cpu <= wall
    assert cpu_share[0] * wall <= cpu <= cpu_share[1] * wall
    # and by the kind the span was given while open
    assert (c["span_wall_us_launch_decode"], c["span_cpu_us_launch_decode"]) == (wall, cpu)
    (r,) = rec.records()
    assert (r.name, r.kind) == ("launch", "decode") and r.dur * 1e6 == pytest.approx(wall, abs=2)


def test_a_cpu_span_without_a_kind_counts_under_its_name_alone():
    rec = trace.Recorder()
    for _ in range(2):
        with rec.span("launch", marks=trace.CPU):
            pass
    assert sorted(rec.counters) == ["span_cpu_us_launch", "span_wall_us_launch"]


def test_every_cpu_span_reads_both_clocks_and_counts_by_the_kind_it_closes_with(clock):
    """No span of the mark is skipped: the two counters are sums over the same spans, all of
    them, so their ratio is the calls' own however coarse the host's CPU clock."""
    rec = trace.Recorder()
    for i in range(17):
        with rec.span("launch", i, "s", trace.CPU) as launch:
            launch.kind = "decode" if i % 8 else "prefill"      # set while the span is open
            clock.t += 0.001
            clock.cpu += 0.0005
    assert clock.cpu_reads == 2 * 17
    assert rec.counters == {"span_wall_us_launch": 17000, "span_cpu_us_launch": 8500,
                            "span_wall_us_launch_prefill": 3000, "span_cpu_us_launch_prefill": 1500,
                            "span_wall_us_launch_decode": 14000, "span_cpu_us_launch_decode": 7000}
    assert len(rec.records()) == 17


def test_a_cpu_clock_that_reads_over_the_wall_clock_is_counted_as_it_reads(clock):
    """A host whose thread CPU clock steps by a scheduler tick charges a 3 ms call 10 ms or
    nothing: the counters carry what was read, and a reader sees a share over 100%."""
    rec = trace.Recorder()
    for i in range(4):
        with rec.span("launch", i, "s", trace.CPU):
            clock.t += 0.003
            clock.cpu += 0.01 if i % 2 else 0.0
    assert (rec.counters["span_wall_us_launch"], rec.counters["span_cpu_us_launch"]) == (12000, 20000)


class _Clock:
    """``time`` as the recorder reads it, moved by hand."""

    def __init__(self):
        self.t, self.cpu, self.cpu_reads = 100.0, 0.0, 0

    def perf_counter(self):
        return self.t

    def thread_time(self):
        self.cpu_reads += 1
        return self.cpu


@pytest.fixture
def clock(monkeypatch):
    hand = _Clock()
    monkeypatch.setattr(trace, "time", hand)
    return hand


def test_an_unmarked_span_reads_no_thread_time(clock):
    rec = trace.Recorder()
    with rec.span("tick", 1, "s", trace.UNIT):
        with rec.span("dispatch", 1, "s"):
            pass
    assert clock.cpu_reads == 0
    with rec.span("launch", 1, "s", trace.CPU):
        pass
    assert clock.cpu_reads == 2


def _unit(rec, clock, uid, children, kind="decode", name="tick", source="s", inside=None,
          like=None):
    """One unit of work on the hand-moved clock: ``children`` are
    ``(name, seconds)`` or ``(name, seconds, [(grandchild, seconds), ...])``."""
    with rec.span(name, uid, source, trace.UNIT) as unit:
        unit.kind, unit.like = kind, like
        for child, seconds, *inner in children:
            with rec.span(child, uid, source):
                for grandchild, part in (inner[0] if inner else ()):
                    with rec.span(grandchild, uid, source):
                        clock.t += part
                    seconds -= part
                clock.t += seconds
                if inside is not None:
                    inside()
        clock.t += 1e-4     # the unit's own time


def _steady(rec, clock, n, seconds=0.01, kind="decode", first=1, **kw):
    for uid in range(first, first + n):
        _unit(rec, clock, uid, [("dispatch", seconds * 0.25), ("device_wait", seconds * 0.75)],
              kind, **kw)
    return first + n


def _stalls(rec):
    return [(r.uid, r.kind) for r in rec.records() if r.name == "stall"]


@pytest.mark.parametrize("typical, long, stalled", [
    (0.01, 0.2, False),      # twenty times the typical tick, and under a quarter of a second
    (0.1, 0.45, False),      # over a quarter of a second, and under five times
    (0.01, 0.3, True),
    (0.1, 0.51, True),
], ids=["times-alone", "seconds-alone", "both", "both-barely"])
def test_a_stall_is_five_times_the_typical_unit_and_a_quarter_second(clock, typical, long, stalled):
    rec = trace.Recorder()
    uid = _steady(rec, clock, 12, typical)
    assert rec.counters["units_stalled_tick"] == 0      # shown as 0 from the first unit
    _unit(rec, clock, uid, [("dispatch", typical * 0.25), ("device_wait", long)])
    assert rec.counters["units_stalled_tick"] == int(stalled)
    assert _stalls(rec) == ([(uid, "decode:device_wait")] if stalled else [])
    if stalled:
        assert rec.counters["stall_us_tick"] == pytest.approx((long + typical * 0.25) * 1e6, rel=0.01)
        (stall,) = [r for r in rec.records() if r.name == "stall"]
        (tick,) = [r for r in rec.records() if r.name == "tick" and r.uid == uid]
        # one record over the unit, beside it: not a child, so no reader of phases sums it
        assert (stall.start, stall.end, stall.path, stall.source) == (tick.start, tick.end, (), "s")
    else:
        assert "stall_us_tick" not in rec.counters


@pytest.mark.parametrize("before", [0, 3, 7])
def test_no_unit_is_a_stall_before_eight_of_its_kind(clock, before):
    rec = trace.Recorder()
    uid = _steady(rec, clock, before)
    _unit(rec, clock, uid, [("device_wait", 2.0)])
    assert rec.counters["units_stalled_tick"] == 0 and _stalls(rec) == []
    # another kind of the same scheduler, and another scheduler, start their own count
    uid = _steady(rec, clock, 8, first=uid + 1)
    _unit(rec, clock, uid, [("device_wait", 2.0)], kind="prefill")
    _unit(rec, clock, uid + 1, [("device_wait", 2.0)], source="other")
    assert rec.counters["units_stalled_tick"] == 0


def test_a_typical_length_is_kept_by_source_name_and_kind(clock):
    rec = trace.Recorder()
    uid = _steady(rec, clock, 10, 0.01, "decode")
    uid = _steady(rec, clock, 10, 0.4, "prefill", first=uid)        # long by nature: none
    uid = _steady(rec, clock, 10, 0.5, name="train_batch", source="engine#0", kind=None, first=uid)
    assert rec.counters == {"units_stalled_tick": 0, "units_stalled_train_batch": 0}
    _unit(rec, clock, uid, [("device_wait", 0.4)], "decode")
    _unit(rec, clock, uid + 1, [("timer_sync", 3.0), ("device_wait", 0.5)], None,
          name="train_batch", source="engine#0")
    assert rec.counters["units_stalled_tick"] == 1 and rec.counters["units_stalled_train_batch"] == 1
    # a unit without a kind is filed under its name
    assert _stalls(rec) == [(uid, "decode:device_wait"), (uid + 1, "train_batch:timer_sync")]


def test_units_of_a_kind_are_held_against_the_units_like_them(clock):
    """reason-sat on the chip, the first run of this rule (PR 53): eight prefill ticks on
    the 16-row rung at ~50 ms, then the pre-roll's whole-shape ticks at 251-276 ms, two of
    them counted as stalls. A tick's ``like`` is the rows its program ran."""
    rec = trace.Recorder()
    uid = _steady(rec, clock, 12, 0.05, "prefill", like=16)
    for seconds in (0.276, 0.251, 0.228, 0.23, 0.229, 0.228, 0.231, 0.228, 0.23):
        uid = _steady(rec, clock, 1, seconds, "prefill", first=uid, like=64)
    assert rec.counters["units_stalled_tick"] == 0 and _stalls(rec) == []
    _unit(rec, clock, uid, [("device_wait", 1.2)], "prefill", like=64)      # and a real one is seen
    _unit(rec, clock, uid + 1, [("device_wait", 0.26)], "prefill", like=16)
    assert _stalls(rec) == [(uid, "prefill:device_wait"), (uid + 1, "prefill:device_wait")]
    # without it, the first whole-shape tick reads as the chip's did
    rec = trace.Recorder()
    uid = _steady(rec, clock, 12, 0.05, "prefill")
    _steady(rec, clock, 1, 0.276, "prefill", first=uid)
    assert rec.counters["units_stalled_tick"] == 1


def test_an_idle_unit_is_never_a_stall_and_feeds_nothing(clock):
    rec = trace.Recorder()
    uid = _steady(rec, clock, 10, 0.001, trace.IDLE)
    _unit(rec, clock, uid, [("admit", 5.0)], trace.IDLE)
    assert rec.counters == {} and _stalls(rec) == [] and rec._typical == {}


def test_a_unit_that_compiled_is_a_recompile_and_not_a_stall(clock):
    rec = trace.Recorder()
    uid = _steady(rec, clock, 10)

    def compile_():
        rec._on_compile_duration("/jax/core/compile/backend_compile_duration", 1.5,
                                 fun_name="jit(decode)")

    _unit(rec, clock, uid, [("dispatch", 2.0)], inside=compile_)
    assert rec.counters["units_stalled_tick"] == 0 and _stalls(rec) == []
    assert rec.counters["recompiles_in_units"] == 1         # already named
    assert [r.uid for r in rec.records() if r.name == "recompile"] == [uid]
    # and it has not made the typical tick longer: the next long one is a stall
    _unit(rec, clock, uid + 1, [("dispatch", 0.3)])
    assert _stalls(rec) == [(uid + 1, "decode:dispatch")]


@pytest.mark.parametrize("children, kind", [
    ([("admit", 0.01), ("dispatch", 0.02), ("device_wait", 0.9), ("commit", 0.05)],
     "decode:device_wait"),
    ([("admit", 0.6), ("dispatch", 0.02), ("device_wait", 0.5)], "decode:admit"),
    # what the longest child spent most of its time in is named in its place
    ([("dispatch", 0.8, [("launch", 0.7), ("account", 0.05)]), ("device_wait", 0.1)],
     "decode:launch"),
    # unless no one child of it holds half of it
    ([("dispatch", 0.8, [("launch", 0.3), ("account", 0.3)]), ("device_wait", 0.1)],
     "decode:dispatch"),
    ([], "decode"),
], ids=["device-wait", "admit", "launch-inside-dispatch", "dispatch-itself", "no-children"])
def test_a_stall_record_names_the_units_longest_child(clock, children, kind):
    rec = trace.Recorder()
    uid = _steady(rec, clock, 9)
    if not children:
        with rec.span("tick", uid, "s", trace.UNIT) as unit:
            unit.kind = "decode"
            clock.t += 1.0
    else:
        # another scheduler's spans between them, and a back-dated record under the unit
        with rec.span("tick", 77, "other", trace.UNIT):
            _unit(rec, clock, uid, children,
                  inside=lambda: rec.record("queue_wait", 0.0, 50.0, uid=uid, source="s"))
    assert _stalls(rec)[-1] == (uid, kind)


def test_a_steady_stream_writes_no_stall_and_follows_a_slow_drift(clock):
    rec = trace.Recorder()
    seconds, uid = 0.05, 1
    for _ in range(400):                     # 1% longer every tick: sixty times as long at the end
        uid = _steady(rec, clock, 1, seconds, first=uid)
        seconds *= 1.01
    assert seconds > 2.5 and rec.counters["units_stalled_tick"] == 0 and _stalls(rec) == []
    # whole-shape prefill ticks among their rung's, 3.5 times as long: none
    for i in range(40):
        uid = _steady(rec, clock, 1, 0.35 if i % 4 == 0 else 0.1, "prefill", first=uid)
    assert rec.counters["units_stalled_tick"] == 0


def test_a_stall_does_not_feed_the_typical_length(clock):
    rec = trace.Recorder()
    uid = _steady(rec, clock, 10)
    for _ in range(5):                      # were they fed, the third would pass for typical
        _unit(rec, clock, uid, [("device_wait", 0.4)])
        uid += 1
    assert rec.counters["units_stalled_tick"] == 5
    assert rec.counters["stall_us_tick"] == pytest.approx(5 * 0.4001e6, rel=0.01)


def test_a_stall_survives_a_small_ring_through_its_counters(clock):
    rec = trace.Recorder(capacity=4)
    uid = _steady(rec, clock, 10)
    _unit(rec, clock, uid, [("dispatch", 0.01), ("device_wait", 1.0)])
    assert [r.name for r in rec.records()] == ["dispatch", "device_wait", "tick", "stall"]
    _steady(rec, clock, 5, first=uid + 1)
    assert _stalls(rec) == [] and rec.dropped > 30          # the ring has turned over
    assert rec.counters["units_stalled_tick"] == 1
    assert rec.counters["stall_us_tick"] == pytest.approx(1.0101e6, rel=0.01)


def test_a_gauge_is_set_and_not_added():
    rec = trace.Recorder()
    rec.gauge("program_operand_leaves", 430)
    rec.gauge("program_operand_leaves", 42)
    rec.count("ticks", 2)
    assert rec.counters == {"program_operand_leaves": 42, "ticks": 2}
