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
  profiler session (``DS_TRACE_STEPS``, the ``trace_profiler`` block, a
  benchmark's ``--trace 1``) it lies on the host plane on the same clock
  as the device's ``XLA Ops`` line. With no session that is one flag test;
* it appends one :class:`Record` to a bounded in-memory ring: two
  ``perf_counter`` reads and one append. The ring is always on: it is the
  operator's flight recorder, and what ``RuntimeTelemetry`` (window
  histograms, the JSONL sink) and the benchmark's per-layer readers reduce.

Counters are plain integers in one dict on the same object. One
process-level instance (:func:`recorder`) is reachable without a handle on
an engine; a record names the engine or scheduler it came from
(``source``), so two in one process do not read each other's.
"""

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

__all__ = ["Record", "Recorder", "recorder", "new_source", "PREFIX"]

#: prefix of every annotation this recorder writes into a profiler trace
PREFIX = "ds:"
#: a 51 s serving run is ~10,000 records (some ten spans a 60 ms tick)
RING_RECORDS = 65536


class Record(NamedTuple):
    """One closed span (or one back-dated interval, see ``Recorder.record``)."""

    seq: int                   # position in the recorder's stream, from 1
    name: str
    start: float               # ``perf_counter`` seconds (``record``: the caller's clock)
    end: float
    path: Tuple[str, ...]      # names of the enclosing spans, outermost first
    uid: Optional[int]         # shared by the spans of one unit of work: tick, step, request
    source: Optional[str]      # the engine or scheduler it came from
    kind: Optional[str]        # what the unit of work turned out to be (a tick's kind)

    @property
    def parent(self) -> Optional[str]:
        """The span that caused this one: the innermost enclosing span."""
        return self.path[-1] if self.path else None

    @property
    def dur(self) -> float:
        return self.end - self.start


class _Span:
    __slots__ = ("_rec", "name", "uid", "source", "kind", "_ann", "_path", "_t0")

    def __init__(self, rec: "Recorder", name: str, uid, source):
        self._rec = rec
        self.name = name
        self.uid = uid
        self.source = source
        self.kind = None    # set while the span is open, once the work is chosen

    def __enter__(self):
        rec = self._rec
        stack = rec._stack()
        self._path = tuple(stack)
        stack.append(self.name)
        rec.last_span = self.name
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._stack().pop()
        rec._append(self.name, self._t0, end, self._path, self.uid, self.source, self.kind)
        return False


class Recorder:
    """Spans into a bounded ring, counters into a dict."""

    def __init__(self, capacity: int = RING_RECORDS):
        self._ring: collections.deque = collections.deque(maxlen=int(capacity))
        self._seq = itertools.count(1)
        self.last_seq = 0
        self.counters: Dict[str, int] = {}
        self.last_span: Optional[str] = None  # liveness breadcrumb (heartbeat payload)
        self._local = threading.local()       # a stack of open spans per thread

    def _stack(self) -> List[str]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = stack = []
            return stack

    def _append(self, name, start, end, path, uid, source, kind) -> None:
        self.last_seq = seq = next(self._seq)
        self._ring.append(Record(seq, name, start, end, path, uid, source, kind))

    def span(self, name: str, uid: Optional[int] = None,
             source: Optional[str] = None) -> _Span:
        """Context manager around one host phase; nests under the span
        that is open on this thread."""
        return _Span(self, name, uid, source)

    def record(self, name: str, start: float, end: float, uid: Optional[int] = None,
               source: Optional[str] = None) -> None:
        """An interval that is over when it becomes known (a request's wait
        for a slot), on the caller's clock. Ring only: a ``TraceAnnotation``
        cannot be back-dated."""
        self._append(name, start, end, (), uid, source, None)

    def count(self, name: str, n: int = 1) -> None:
        counters = self.counters
        counters[name] = counters.get(name, 0) + n

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


def recorder() -> Recorder:
    """The process's recorder."""
    return _RECORDER


def new_source(label: str) -> str:
    """A name no other engine or scheduler of this process records under."""
    return f"{label}#{next(_sources)}"
