"""The program's one recorder: host spans and counters, on the profiler's clock.

TPU steps dispatch asynchronously: the Python line that "runs" a step
returns in microseconds while XLA executes in the background, so a
callback *inside* the step is both impossible and forbidden here
(graft-lint R003/R015 gate that instrumentation never enters the traced
program). What the host CAN observe, and what this recorder times, are the
phases the engine and the serving scheduler themselves drive: staging,
dispatch, the blocking read-back, the commit loop, checkpoint publish.

A span does two things on entry and exit:

* it opens ``jax.profiler.TraceAnnotation("ds:<name>")``, so that in any
  profiler session (the ``trace_profiler`` block, a benchmark's
  ``--trace 1``) it lies on the host plane on the same clock
  as the device's ``XLA Ops`` line. With no session that is one flag test;
* it appends one :class:`Record` to a bounded in-memory ring: two
  ``perf_counter`` reads and one append. The ring is always on: it is the
  operator's flight recorder, and what ``RuntimeTelemetry`` (window
  histograms, the JSONL sink) and the benchmark's per-layer readers reduce.

Counters are plain integers in one dict on the same object. One
process-level instance (:func:`recorder`) is reachable without a handle on
an engine; a record names the engine or scheduler it came from
(``source``), so two in one process do not read each other's.

The process's recorder also hears JAX's compile events
(``jax.monitoring``): each trace, lowering, backend compile and cache
retrieval becomes one back-dated record under the span that was open on
the thread, and adds its microseconds to counters named by the **root**
span it fell under (:data:`COMPILE_EVENTS`). So a start divides into the
spans of the entry points (marked :data:`TOTAL`) and, inside each,
the time JAX held the interpreter and the time the compiler or the cache
took; and a compile in the middle of serving or training is a
``recompile`` record that names the tick or step, the phase and the
function.

A span is wall time. Three things say what wall time cannot: a span marked
:data:`CPU` reads the thread's CPU clock beside it, so a call that waits is
told from one that computes; a unit of work (:data:`UNIT`: a tick, a step)
that runs far over the typical length of its kind is counted and leaves a
``stall`` record that names the phase it hung under, so that a hang no
median shows is on record (:meth:`Recorder._unit_closed`); and a gauge
(:meth:`Recorder.gauge`) is a counter that is set, for what the newest
engine or scheduler says of itself.
"""

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Set, Tuple

import jax
import jax.monitoring

__all__ = ["Record", "Recorder", "recorder", "new_source", "imported", "PREFIX",
           "COMPILE_EVENTS", "UNIT", "WARMS", "TOTAL", "CPU", "IDLE"]

#: prefix of every annotation this recorder writes into a profiler trace
PREFIX = "ds:"
#: what the busiest run measured writes, and a quarter: the benchmark's chat
#: cell, 136,172 records from its start to its last reader (a working tick
#: of 4.7 ms writes a dozen, and an idle ``step()`` three, a millisecond apart
#: while the runner waits for an arrival); ~240 bytes a record, 42 MB full
RING_RECORDS = 172032
#: JAX's compile events: the record each becomes, and the counter its
#: microseconds go to, which ends in the name of the root span it fell
#: under (``setup_backend_us_warmup``). The cache's retrieval lies inside
#: the backend compile that asked for it, so it is in no sum of the others
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("compile_trace", "setup_trace_lower_us"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("compile_lower", "setup_trace_lower_us"),
    "/jax/core/compile/backend_compile_duration": ("compile_backend", "setup_backend_us"),
    "/jax/compilation_cache/cache_retrieval_time_sec": ("compile_cache_load",
                                                        "setup_cache_load_us"),
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: a model's trace emits one event for every jitted function it calls
#: (``add``, ``softmax``): thousands, each inside the trace of the program.
#: They are counted, once, and those shorter than this stay out of the ring
_RING_COMPILE_S = 5e-3
#: an interval is inside another that starts this much after it: the two
#: clocks are read some microseconds after the interval ended
_NEST_SLACK_S = 1e-4
#: closed intervals kept for one that may contain them. A model's trace holds
#: some thousand until it ends; a process that retraces for ever holds no more
_NEST_KEPT = 16384
#: a unit of work (a span marked ``UNIT``) is a stall where it runs over
#: ``_STALL_TIMES`` the typical length of the units like it (its source, name,
#: kind and ``like``: a tick's is the rows its program ran, since a
#: whole-shape prefill tick is 3-5 times its rung's) AND over
#: ``_STALL_MIN_S``. The typical length is a mean over the first
#: ``_STALL_AFTER_UNITS`` such units, none of which can be a stall, and from
#: then on weighted ``_STALL_WEIGHT`` to the newest unit that was no stall
_STALL_TIMES, _STALL_MIN_S, _STALL_AFTER_UNITS, _STALL_WEIGHT = 5.0, 0.25, 8, 0.125
#: what a span's opener may say of it (``Recorder.span``)
UNIT, WARMS, TOTAL, CPU = 1, 2, 4, 8
#: the ``kind`` of a unit that found no work to do: never a stall
IDLE = "idle"


class Record(NamedTuple):
    """One closed span (or one back-dated interval, see ``Recorder.record``)."""

    seq: int                   # position in the recorder's stream, from 1
    name: str
    start: float               # ``perf_counter`` seconds (``record``: the caller's clock)
    end: float
    path: Tuple[str, ...]      # names of the enclosing spans, outermost first
    uid: Optional[int]         # shared by the spans of one unit of work: tick, step, request
    source: Optional[str]      # the engine or scheduler it came from
    kind: Optional[str]        # a tick's kind, a compile's function, an import's package

    @property
    def parent(self) -> Optional[str]:
        """The span that caused this one: the innermost enclosing span."""
        return self.path[-1] if self.path else None

    @property
    def dur(self) -> float:
        return self.end - self.start


class _Span:
    __slots__ = ("_rec", "name", "uid", "source", "kind", "like", "marks", "_ann", "_path", "_t0",
                 "end", "_cpu0", "_seq0", "compiled")

    def __init__(self, rec: "Recorder", name: str, uid, source, marks):
        self._rec = rec
        self.name = name
        self.uid = uid
        self.source = source
        self.kind = None    # set while the span is open, once the work is chosen
        self.like = None    # a unit's: what units of its kind must share to take as long
        self.marks = marks  # what the opener said of it: see ``Recorder.span``
        self.end = None     # ``perf_counter`` at the close
        self._cpu0 = None   # the thread's CPU clock at the opening, of a span marked ``CPU``

    def __enter__(self):
        rec = self._rec
        stack = rec._local.stack
        if stack:
            parent = stack[-1]
            self._path = parent._path + (parent.name,)
        else:
            self._path = ()
        stack.append(self)
        rec.last_span = self.name
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        marks = self.marks
        if marks & UNIT:
            self._seq0 = rec.last_seq   # what the ring holds above it when it closes is its own
            self.compiled = False       # set by a backend compile under it
        self._t0 = time.perf_counter()
        if marks & CPU:     # inside the wall clock's reads: the CPU counted lies inside the wall
            self._cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu0 = self._cpu0
        if cpu0 is not None:
            cpu = time.thread_time() - cpu0
        self.end = end = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._local.stack.pop()
        rec._append(self.name, self._t0, end, self._path, self.uid, self.source, self.kind)
        marks = self.marks
        if marks:
            if marks & TOTAL and not self._path:
                rec.count("setup_span_us_" + self.name, int((end - self._t0) * 1e6))
            if marks & WARMS:
                rec._warmed.add(self.source)
            if cpu0 is not None:
                wall, cpu = int((end - self._t0) * 1e6), int(cpu * 1e6)
                names = (self.name,) if self.kind is None else (self.name,
                                                                f"{self.name}_{self.kind}")
                for name in names:
                    rec.count("span_wall_us_" + name, wall)
                    rec.count("span_cpu_us_" + name, cpu)
            if marks & UNIT:
                rec._unit_closed(self)
        return False

    @property
    def start(self) -> float:
        """``perf_counter`` at the opening."""
        return self._t0

    @property
    def below(self) -> Tuple[str, ...]:
        """The path of what opens or falls inside this span."""
        return self._path + (self.name,)


class _Thread(threading.local):
    """What the recorder keeps for each thread."""

    def __init__(self):
        self.stack: List[_Span] = []    # the open spans, outermost first
        # closed compile and import intervals that a later one may turn out
        # to contain, oldest first: (start, microseconds, counter). One stays
        # for each compile or import that no other contained, ``_NEST_KEPT`` at most
        self.nest: List[tuple] = []
        self.cache_hit = False          # the cache answered the compile in progress
        # ((unit span, its kind), is a compile under it a recompile)
        self.unit = (None, False)


class Recorder:
    """Spans into a bounded ring, counters into a dict."""

    def __init__(self, capacity: int = RING_RECORDS):
        self._ring: collections.deque = collections.deque(maxlen=int(capacity))
        self._seq = itertools.count(1)
        self.last_seq = 0
        self.counters: Dict[str, int] = {}
        self.last_span: Optional[str] = None  # liveness breadcrumb (heartbeat payload)
        self._local = _Thread()
        self._warmed: Set[Optional[str]] = set()    # sources that closed a span that ``WARMS``
        # (source, name, kind, like) of a unit -> [units that fed it, their typical seconds]
        self._typical: Dict[tuple, list] = {}

    def _append(self, name, start, end, path, uid, source, kind) -> None:
        self.last_seq = seq = next(self._seq)
        # a NamedTuple's own constructor costs twice the tuple's, a span
        self._ring.append(tuple.__new__(Record, (seq, name, start, end, path, uid, source, kind)))

    def span(self, name: str, uid: Optional[int] = None, source: Optional[str] = None,
             marks: int = 0) -> _Span:
        """Context manager around one host phase; nests under the span
        that is open on this thread. ``marks`` is what its opener says of
        it, any of these or'ed together:

        * :data:`UNIT`: it is one unit of steady work (a tick, a step). A
          backend compile under it is a recompile once its source has
          finished a unit of the same name and kind, or a span that warms;
        * :data:`WARMS`: when it closes, its source has compiled what its
          units run;
        * :data:`TOTAL`: where it is a root (an entry point of the start),
          its microseconds also go to the counter ``setup_span_us_<name>``:
          what a start cost is still known when the ring has turned over;
        * :data:`CPU`: it reads the thread's CPU clock (a system call) beside
          the wall clock and adds both to ``span_wall_us_<name>`` and
          ``span_cpu_us_<name>`` (and, where it has a ``kind`` when it
          closes, to ``..._<name>_<kind>``): wall well over CPU means what
          it called waited, equal means it computed. The sums are as fine as
          the host's CPU clock: one that steps by a scheduler tick of 10 ms
          tells a call that computes from one that waits, and no finer.

        A span marked :data:`UNIT` that runs far over the typical length of
        its kind is counted and recorded as a stall: :meth:`_unit_closed`."""
        return _Span(self, name, uid, source, marks)

    def record(self, name: str, start: float, end: float, uid: Optional[int] = None,
               source: Optional[str] = None, kind: Optional[str] = None) -> None:
        """An interval that is over when it becomes known (a request's wait
        for a slot, a compile), on the caller's clock. It is filed under
        the span open on this thread, and takes that span's ``uid`` and
        ``source`` where it is given none. Ring only: a ``TraceAnnotation``
        cannot be back-dated."""
        stack = self._local.stack
        if stack:
            inner = stack[-1]
            self._append(name, start, end, inner.below, inner.uid if uid is None else uid,
                         inner.source if source is None else source, kind)
        else:
            self._append(name, start, end, (), uid, source, kind)

    def _add_once(self, counter: str, start: float, end: float) -> None:
        """Add an interval's microseconds to ``counter``, and take back
        those of the earlier intervals of this thread that lie inside it (a
        jitted function traced inside another's trace reports first): they
        are its children, so no sum of these counters exceeds the span the
        intervals fell under."""
        nest = self._local.nest
        while nest and nest[-1][0] >= start - _NEST_SLACK_S:
            _, us, inside = nest.pop()
            self.count(inside, -us)
        if len(nest) >= _NEST_KEPT:
            del nest[:_NEST_KEPT // 2]
        us = int((end - start) * 1e6)
        nest.append((start, us, counter))
        self.count(counter, us)

    def _is_recompile(self, unit: Optional[_Span]) -> bool:
        """Whether a compile under ``unit``, the innermost open span marked
        :data:`UNIT`, falls in steady work: its source has
        already closed a span that :data:`WARMS`, or finished a unit of the same
        name and kind. Read from the ring when a unit compiles, so that no
        tick or step pays to keep it known."""
        if unit is None:
            return False
        key, known = (unit, unit.kind), self._local.unit
        if known[0] != key:     # a tick's kind is set once its work is chosen
            done = unit.source in self._warmed or any(
                r.name == unit.name and r.source == unit.source and r.kind == unit.kind
                for r in reversed(self._ring))
            self._local.unit = known = (key, done)
        return known[1]

    def _on_compile_duration(self, event: str, duration: float, fun_name: Optional[str] = None,
                             **_) -> None:
        """What the process's recorder does with a duration event of
        ``jax.monitoring``: see :data:`COMPILE_EVENTS`."""
        found = COMPILE_EVENTS.get(event)
        if found is None:
            return
        name, counter = found
        end = time.perf_counter()
        start = end - duration
        local = self._local
        stack = local.stack
        unit = next((span for span in reversed(stack) if span.marks & UNIT), None)
        if unit is not None:
            unit.compiled = True    # its length is the compiler's: no stall, and not typical
        recompile = self._is_recompile(unit)
        if not stack:
            where = "compile_outside_us"
        elif recompile:
            where = "recompile_us"
        else:
            where = f"{counter}_{stack[0].name}"
        if name == "compile_cache_load":
            if stack and not recompile:
                self.count(where, int(duration * 1e6))
        else:
            self._add_once(where, start, end)
        if name != "compile_trace" or duration >= _RING_COMPILE_S:
            self.record(name, start, end, kind=fun_name)
        if name != "compile_backend":
            return
        hit, local.cache_hit = local.cache_hit, False
        if recompile:
            # the operator's answer to "which step recompiled, and what":
            # uid is the tick or step, the parent the phase, kind the function
            self.count("recompiles_in_units")
            self.record("recompile", start, end, kind=fun_name)
        elif stack:
            self.count(f"setup_programs_loaded_{stack[0].name}")
            self.count(f"setup_cache_hits_{stack[0].name}", int(hit))

    def _on_compile_event(self, event: str, **_) -> None:
        """A hit of the persistent cache comes before the duration of the
        backend compile it answers."""
        if event == _CACHE_HIT_EVENT:
            self._local.cache_hit = True

    def _unit_closed(self, unit: _Span) -> None:
        """A unit of steady work has closed: hold it against the typical
        length of the units like it, which it then feeds unless it
        is a stall (``_STALL_TIMES``). An idle unit and one that compiled
        (a first step, a recompile: named already) are neither. A steady
        unit pays one lookup and one multiply-add."""
        kind = unit.kind
        if kind == IDLE or unit.compiled:
            return
        dur = unit.end - unit._t0
        key = (unit.source, unit.name, kind, unit.like)
        known = self._typical.get(key)
        if known is None:
            self._typical[key] = [1, dur]
            self.count("units_stalled_" + unit.name, 0)     # shown as 0, not left out
            return
        n, typical = known
        if n < _STALL_AFTER_UNITS:
            known[0] = n + 1
            known[1] = typical + (dur - typical) / (n + 1)
        elif dur > _STALL_TIMES * typical and dur > _STALL_MIN_S:
            self._stalled(unit)
        else:
            known[1] = typical + (dur - typical) * _STALL_WEIGHT

    def _stalled(self, unit: _Span) -> None:
        """Count a stalled unit, and write one ``stall`` record over it,
        beside it in the ring, that says under what it hung: ``kind`` is the
        unit's kind (its name where it has none) and its longest direct
        child, or what that child spent most of its time in
        (``decode:device_wait``; ``prefill:launch``, inside ``dispatch``):
        a child's longest child, while one holds over half of its parent.
        Read from the ring, only now."""
        start, end = unit._t0, unit.end
        self.count("units_stalled_" + unit.name)
        self.count("stall_us_" + unit.name, int((end - start) * 1e6))
        below = unit.below
        under = [r for r in itertools.islice(reversed(self._ring), self.last_seq - unit._seq0)
                 if r.uid == unit.uid and r.source == unit.source
                 and r.path[:len(below)] == below]
        inner, dur = None, end - start
        while True:
            inside = [r for r in under if r.path == below and start <= r.start and r.end <= end]
            longest = max(inside, key=lambda r: r.dur, default=None)
            if longest is None or (inner is not None and longest.dur <= dur / 2):
                break
            inner, below = longest.name, below + (longest.name,)
            start, end, dur = longest.start, longest.end, longest.dur
        kind = unit.kind or unit.name
        self._append("stall", unit._t0, unit.end, unit._path, unit.uid, unit.source,
                     kind if inner is None else f"{kind}:{inner}")

    def count(self, name: str, n: int = 1) -> None:
        counters = self.counters
        counters[name] = counters.get(name, 0) + n

    def gauge(self, name: str, n: int) -> None:
        """A counter that is set, not added to: what the newest of its
        kind says (two schedulers in a process do not sum)."""
        self.counters[name] = n

    @property
    def dropped(self) -> int:
        """Records the ring has pushed out since the recorder was made."""
        return self.last_seq - len(self._ring)

    def records(self, source: Optional[str] = None) -> List[Record]:
        """What the ring holds, oldest first (children before their parent:
        a span is recorded when it closes)."""
        if source is None:
            return list(self._ring)
        return [r for r in self._ring if r.source == source]

    def since(self, cursor: int, source: Optional[str] = None) -> Tuple[List[Record], int]:
        """Records with ``seq`` above ``cursor``, oldest first, and how many
        of that stretch the ring no longer holds."""
        out = []
        for r in reversed(self._ring):
            if r.seq <= cursor:
                break
            if source is None or r.source == source:
                out.append(r)
        out.reverse()
        oldest = self._ring[0].seq if self._ring else self.last_seq + 1
        return out, max(0, oldest - cursor - 1)


_RECORDER = Recorder()
_sources = itertools.count()


def _hear_duration(event: str, duration: float, **kwargs) -> None:
    _RECORDER._on_compile_duration(event, duration, **kwargs)


def _hear_event(event: str, **kwargs) -> None:
    _RECORDER._on_compile_event(event, **kwargs)


# once a process, here: whatever recorder is the process's when JAX compiles
# hears it, however many engines and schedulers the process builds
jax.monitoring.register_event_duration_secs_listener(_hear_duration)
jax.monitoring.register_event_listener(_hear_event)


def recorder() -> Recorder:
    """The process's recorder."""
    return _RECORDER


def new_source(label: str) -> str:
    """A name no other engine or scheduler of this process records under."""
    return f"{label}#{next(_sources)}"


def imported(package: str, start: float) -> None:
    """The last line of a package's ``__init__`` calls this with the
    ``perf_counter`` read on its first: one ``import`` record, and its
    microseconds in ``setup_import_us`` (a package imported inside another's
    import is counted once)."""
    end = time.perf_counter()
    _RECORDER._add_once("setup_import_us", start, end)
    _RECORDER.record("import", start, end, kind=package)
