"""What the per-layer readers of the program's own spans share. The
program keeps one recorder (``deepspeed_tpu/utils/trace.py``): a ring of
closed spans and a dict of counters, reachable without a handle on the
engine. The readers under ``layer_metrics/`` reduce that ring after the
run; at a commit whose program has no recorder they find nothing and
return None.

The runner does not tell the program when the window opens, so a reader
takes its percentile over what the ring holds of the newest scheduler (or
engine), and leaves out:

* idle ticks;
* set-up: the two checked requests (``runners/serve.py::_checked_requests``)
  and every tick before the one that admitted the first request after
  them;
* the requests admitted with that first one, to an empty server at the
  start of the pre-roll: they wait for nothing and nothing is decoding
  beside them;
* whatever follows the first gap of more than ``STALL_S`` between two
  ticks: the profiler's start stalls the loop for seconds, and the ticks
  after it run under the profiler (the runner takes its own host-clock
  numbers from the part before it, too).

What is left is the pre-roll and the untraced part of the window. Training
steps are all kept: warm-up and the traced steps are a few of some two
hundred, and every number is a median.
"""

from benchmarks.lib import harness, stats

SETUP_REQUESTS = 2
STALL_S = 0.5


def ring():
    """(records, counters) of the program's recorder; ([], {}) where the
    program has none."""
    try:
        from deepspeed_tpu.utils import trace
    except ImportError:
        return [], {}
    rec = trace.recorder()
    return rec.records(), dict(rec.counters)


def _units(records, root):
    """The newest source's ``root`` spans, oldest first, each with its
    direct children summed by name: [(root record, {child: seconds})]."""
    roots = [r for r in records if r.name == root]
    if not roots:
        return []
    source = roots[-1].source
    children = {}
    for r in records:
        if r.source == source and r.path == (root,):
            by_name = children.setdefault(r.uid, {})
            by_name[r.name] = by_name.get(r.name, 0.0) + (r.end - r.start)
    return [(r, children.get(r.uid, {})) for r in roots if r.source == source]


def serving():
    """The steady ticks and requests of the newest scheduler, or None:
    ``{"ticks": [{"kind", "tick_ms", "host_ms", "phases": {name: ms}}],
    "queue_wait_ms": [...], "prefill_wait_ms": [...]}``. A tick's host
    time is the ``tick`` span less its ``device_wait`` children."""
    records, _ = ring()
    units = _units(records, "tick")
    if not units:
        return None
    source = units[0][0].source
    admitted = [r for r in records if r.name == "queue_wait" and r.source == source]
    if len(admitted) <= SETUP_REQUESTS:
        return None
    # positions in the ring, not times: the request records are on the
    # scheduler's clock, which need not be the spans'
    first = admitted[SETUP_REQUESTS]
    ticks, last = [], None
    for tick, phases in units:
        if tick.seq < first.seq:
            continue
        if last is not None and tick.start - last.end > STALL_S:
            break
        last = tick
        if tick.kind == "idle":
            continue
        wait = phases.get("device_wait", 0.0)
        ticks.append({"kind": tick.kind, "tick_ms": (tick.end - tick.start) * 1e3,
                      "host_ms": (tick.end - tick.start - wait) * 1e3,
                      "phases": {name: secs * 1e3 for name, secs in phases.items()}})
    if last is None:
        return None
    steady = {r.uid for r in admitted[SETUP_REQUESTS:] if r.end != first.end}
    waits = {name: [(r.end - r.start) * 1e3 for r in records
                    if r.name == name and r.source == source and r.uid in steady
                    and r.seq < last.seq]
             for name in ("queue_wait", "prefill_wait")}
    return {"ticks": ticks, "queue_wait_ms": waits["queue_wait"],
            "prefill_wait_ms": waits["prefill_wait"]}


def _split(units_ms):
    """p50 and summed seconds of every phase over ticks or steps given as
    ``{"phases": {name: ms}, ...}``."""
    names = sorted({name for u in units_ms for name in u["phases"]})
    return {name: {"p50_ms": stats.percentile([u["phases"].get(name, 0.0) for u in units_ms], 50),
                   "sum_s": sum(u["phases"].get(name, 0.0) for u in units_ms) / 1e3}
            for name in names}


def sched_host_ms_p50():
    """Host time per non-idle tick, p50; logs the whole split by tick kind."""
    found = serving()
    if not found or not found["ticks"]:
        return None
    by_kind = {}
    for tick in found["ticks"]:
        by_kind.setdefault(tick["kind"], []).append(tick)
    harness.log(program_tick_split={
        kind: {"ticks": len(ticks),
               "tick_ms_p50": stats.percentile([t["tick_ms"] for t in ticks], 50),
               "host_ms_p50": stats.percentile([t["host_ms"] for t in ticks], 50),
               "tick_sum_s": sum(t["tick_ms"] for t in ticks) / 1e3,
               "phases": _split(ticks)}
        for kind, ticks in sorted(by_kind.items())})
    return stats.percentile([t["host_ms"] for t in found["ticks"]], 50)


def device_wait_ms_p50(kind):
    """The blocking read-back of ticks of one kind, p50; logs it beside the
    same ticks' host time and whole length, for the runner's outside number."""
    found = serving()
    ticks = [t for t in (found["ticks"] if found else []) if t["kind"] == kind]
    if not ticks:
        return None
    waits = [t["phases"].get("device_wait", 0.0) for t in ticks]
    harness.log(program_ticks_of_kind={
        "kind": kind, "ticks": len(ticks), "device_wait_ms_p50": stats.percentile(waits, 50),
        "host_ms_p50": stats.percentile([t["host_ms"] for t in ticks], 50),
        "tick_ms_p50": stats.percentile([t["tick_ms"] for t in ticks], 50),
        "device_wait_sum_s": sum(waits) / 1e3})
    return stats.percentile(waits, 50)


def tick_ms_p50(kind):
    """The whole ``tick`` span of the steady ticks of one kind (its host
    time and its ``device_wait`` together), p50; None without such ticks.
    What a tick roofline divides by: where a program is always in flight the
    ticks follow each other as the programs do, so a tick's length is its
    program's, and a least time over it cannot pass 100% however short the
    host's share of the tick grows (``device_wait`` alone shrinks with the
    program under an unchanged host, and the share then passes 100)."""
    found = serving()
    return stats.percentile([t["tick_ms"] for t in (found["ticks"] if found else [])
                             if t["kind"] == kind], 50)


def request_wait_ms(name, p):
    """A percentile of ``queue_wait`` (arrival to admission) or
    ``prefill_wait`` (admission to first token) over the steady requests."""
    found = serving()
    values = found[name + "_ms"] if found else []
    if not values:
        return None
    harness.log(program_request_wait={
        "span": name, "requests": len(values), "p50_ms": stats.percentile(values, 50),
        "p90_ms": stats.percentile(values, 90), "max_ms": max(values),
        "sum_s": sum(values) / 1e3})
    return stats.percentile(values, p)


def counter_ratio_pct(part, whole):
    """100 x one counter over another, both totals of the process (set-up's
    two checked requests included: a few ticks of a run's hundreds)."""
    _, counters = ring()
    if not counters.get(whole):
        return None
    harness.log(program_counters=counters)
    return 100.0 * counters.get(part, 0) / counters[whole]


def train_host_ms_p50():
    """Host time per ``train_batch``: the span less its ``device_wait``
    child, p50 over every step the ring holds; logs the split by phase."""
    records, _ = ring()
    steps = [{"host_ms": (step.end - step.start - phases.get("device_wait", 0.0)) * 1e3,
              "phases": dict({name: secs * 1e3 for name, secs in phases.items()},
                             train_batch=(step.end - step.start) * 1e3)}
             for step, phases in _units(records, "train_batch")]
    if not steps:
        return None
    harness.log(program_step_split={"steps": len(steps), "phases": _split(steps)})
    return stats.percentile([s["host_ms"] for s in steps], 50)
