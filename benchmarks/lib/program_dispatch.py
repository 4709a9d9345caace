"""What the per-layer readers of the host's side of a tick share (ISSUE 53).
Inside the scheduler's ``dispatch`` span the program's recorder
(``deepspeed_tpu/utils/trace.py``) now holds two children and, beside the
tick, what the program saw of the device under it:

* ``launch``: the jitted call alone, marked ``CPU``: it reads the thread's
  CPU clock beside the wall clock and adds both to counters
  ``span_wall_us_launch`` / ``span_cpu_us_launch`` (and
  ``..._launch_decode``, ``..._launch_prefill``). Wall well over CPU: the
  call waited; equal: it computed. The split line prints the ratio as it
  was read (``launch_cpu_pct``) and no per-layer metric reads it: the chip
  tool's host steps that clock by 10 ms and has read 92-125% for calls that
  compute (``PERF.md`` section 7);
* ``account``: ``scheduler._dispatched``'s bookkeeping; what is left of
  ``dispatch`` is the sampling key's split and the span's own cost;
* gauges ``program_operand_leaves`` / ``program_operand_bytes`` (the served
  tree and the slot cache every call is handed, leaf by leaf) and
  ``program_host_operands_<program>`` (the host arrays a tick hands it);
* ``device_dry`` records and ``ticks_device_dry*`` / ``device_dry_us_*``: a
  program dispatched behind another looks at that other's tokens
  (``is_ready()``) entering ``build_inputs``, entering ``dispatch`` and when
  ``launch`` returns. Ended at the last look: the device had nothing queued
  from no later than the first look that saw it ended (the record's start,
  the lower bound) and no earlier than the look before that (the upper
  bound, which this reader rebuilds from the tick's spans) until the launch
  returned. The record's ``kind`` is the phase that ended with that first
  look: ``admit``, ``build_inputs`` or ``launch``;
* ``stall`` records and ``units_stalled_<name>``: a tick or a training step
  that ran over five times the typical length of its kind and over 0.25 s.

The steady ticks are chosen as ``program_spans.serving()`` chooses them (a
test holds the two counts together). Every reader returns None on a program
without the span or counter it reads (the parent).
"""

from benchmarks.lib import harness, program_spans, stats

#: the state of the device under a launch, by the ``kind`` of the tick's
#: ``device_dry`` record: the program in flight ended before the call began,
#: or while it ran; with no record it outlasted the call (the device was fed)
_ENDED = {"admit": "ended_before", "build_inputs": "ended_before", "launch": "ended_during"}


def steady_ticks(records):
    """[(tick record, {direct child: seconds})] of the newest scheduler's
    steady ticks, idle ones among them; [] where there are none. The rule
    is ``program_spans.serving``'s: from the tick that admitted the first
    request after set-up's two until the first gap of ``STALL_S``."""
    units = program_spans._units(records, "tick")
    if not units:
        return []
    source = units[0][0].source
    admitted = [r for r in records if r.name == "queue_wait" and r.source == source]
    if len(admitted) <= program_spans.SETUP_REQUESTS:
        return []
    first = admitted[program_spans.SETUP_REQUESTS]
    out, last = [], None
    for tick, phases in units:
        if tick.seq < first.seq:
            continue
        if last is not None and tick.start - last.end > program_spans.STALL_S:
            break
        last = tick
        out.append((tick, phases))
    return out


def _working(records):
    """The steady non-idle ticks as dicts: the tick's record and direct
    children, its ``dispatch``'s children by name (seconds), its
    ``device_dry`` record if it has one, and the two spans the upper bound
    of a dry stretch starts from."""
    ticks = [(t, p) for t, p in steady_ticks(records) if t.kind != "idle"]
    if not ticks:
        return []
    source = ticks[0][0].source
    wanted = {t.uid for t, _ in ticks}
    inner, dry, starts = {}, {}, {}
    for r in records:
        if r.source != source or r.uid not in wanted:
            continue
        if r.path == ("tick", "dispatch"):
            by_name = inner.setdefault(r.uid, {})
            by_name[r.name] = by_name.get(r.name, 0.0) + (r.end - r.start)
        elif r.name == "device_dry":
            dry[r.uid] = r
        elif r.path == ("tick",) and r.name in ("build_inputs", "dispatch"):
            starts.setdefault(r.uid, {})[r.name] = r.start
    return [{"tick": t, "phases": p, "inner": inner.get(t.uid, {}), "dry": dry.get(t.uid),
             "starts": starts.get(t.uid, {})} for t, p in ticks]


def _dry_bounds(unit):
    """(least, most) seconds the device had nothing queued under a tick:
    (0, 0) for a tick with no ``device_dry`` record."""
    dry, tick = unit["dry"], unit["tick"]
    if dry is None:
        return 0.0, 0.0
    # the look before the one that saw the program ended
    before = {"admit": tick.start, "build_inputs": unit["starts"].get("build_inputs"),
              "launch": unit["starts"].get("dispatch")}.get(dry.kind)
    return dry.end - dry.start, dry.end - (dry.start if before is None else before)


def _p50_sum(values_ms):
    return {"p50_ms": stats.percentile(values_ms, 50), "sum_s": sum(values_ms) / 1e3}


def ring_state():
    """What the ring holds of the run: records written, pushed out, the age
    of the oldest it still holds, its capacity."""
    from deepspeed_tpu.utils import trace
    rec = trace.recorder()
    records = rec.records()
    return {"written": rec.last_seq, "held": len(records), "dropped": rec.dropped,
            "oldest_age_s": records[-1].end - records[0].start if records else 0.0,
            "RING_RECORDS": trace.RING_RECORDS}


def stalls(records):
    """Every ``stall`` record the ring still holds: the unit, under what it
    hung, how long, and its second after the oldest record held."""
    origin = records[0].start if records else 0.0
    return [{"unit": r.uid, "source": r.source, "kind": r.kind, "at_s": r.start - origin,
             "ms": (r.end - r.start) * 1e3} for r in records if r.name == "stall"]


def _log_split(units, records, counters):
    """The ``program_dispatch_split`` line: see the module's docstring and
    the keys below."""
    by_kind = {}
    for u in units:
        by_kind.setdefault(u["tick"].kind, []).append(u)
    split = {}
    for kind, of_kind in sorted(by_kind.items()):
        ms = {name: [] for name in ("dispatch", "launch", "account", "rest", "tick_self")}
        for u in of_kind:
            dispatch, inner, tick = u["phases"].get("dispatch"), u["inner"], u["tick"]
            # a tick's self time: what no span names (the ``_count_*`` helpers,
            # the list builds between phases, the spans' own cost)
            ms["tick_self"].append((tick.end - tick.start - sum(u["phases"].values())) * 1e3)
            if dispatch is None or "launch" not in inner:
                continue
            launch, account = inner["launch"], inner.get("account", 0.0)
            for name, secs in (("dispatch", dispatch), ("launch", launch), ("account", account),
                               ("rest", dispatch - launch - account)):
                ms[name].append(secs * 1e3)
        split[kind] = dict({name: _p50_sum(v) for name, v in ms.items()}, ticks=len(of_kind),
                           dispatched=len(ms["launch"]))
    ahead = bool(counters.get("ticks_dispatched_ahead"))
    by_state = {}
    for u in units:
        if "launch" not in u["inner"]:
            continue
        if u["dry"] is not None:
            state = _ENDED.get(u["dry"].kind, u["dry"].kind)
        elif ahead and "device_wait" in u["phases"]:
            state = "ended_after"
        else:
            state = "nothing_in_flight"     # dispatched into an empty scheduler
        by_state.setdefault(state, []).append(u["inner"]["launch"] * 1e3)
    wall, cpu = counters.get("span_wall_us_launch", 0), counters.get("span_cpu_us_launch", 0)
    leaves = counters.get("program_operand_leaves")
    launches = [u["inner"]["launch"] * 1e6 for u in units if "launch" in u["inner"]]
    bounds = [_dry_bounds(u) for u in units]
    least, most = sum(b[0] for b in bounds), sum(b[1] for b in bounds)
    ticks_s = sum(u["tick"].end - u["tick"].start for u in units)
    harness.log(program_dispatch_split={
        "by_kind": split,
        "launch_wall_us": wall, "launch_cpu_us": cpu,
        "launch_cpu_pct": 100.0 * cpu / wall if wall else None,
        "launch_by_program": {
            key[len("span_wall_us_launch_"):]: {
                "wall_us": value, "cpu_us": counters.get(key.replace("_wall_", "_cpu_"), 0)}
            for key, value in sorted(counters.items()) if key.startswith("span_wall_us_launch_")},
        "launch_ms_by_device_state": {
            state: {"ticks": len(v), "p50_ms": stats.percentile(v, 50), "sum_s": sum(v) / 1e3}
            for state, v in sorted(by_state.items())},
        "operand_leaves": leaves, "operand_bytes": counters.get("program_operand_bytes"),
        "host_operands": {key[len("program_host_operands_"):]: value
                          for key, value in sorted(counters.items())
                          if key.startswith("program_host_operands_")},
        "launch_us_per_leaf": (stats.percentile(launches, 50) / leaves
                               if leaves and launches else None),
        "device_dry": {
            "process": {key: value for key, value in sorted(counters.items())
                        if key.startswith(("ticks_device_dry", "device_dry_us_"))
                        or key in ("ticks_dispatched", "ticks_dispatched_ahead")},
            "steady": {"ticks": len(units), "ticks_dry": sum(u["dry"] is not None for u in units),
                       "ticks_s": ticks_s, "dry_s_min": least, "dry_s_max": most,
                       "pct_min": 100.0 * least / ticks_s, "pct_max": 100.0 * most / ticks_s}},
        "units_stalled": {key: value for key, value in sorted(counters.items())
                          if key.startswith(("units_stalled_", "stall_us_"))},
        "stalls": stalls(records), "ring": ring_state()})


def launch_ms_p50():
    """p50 of the ``launch`` span over the steady non-idle ticks that
    dispatched a program; logs the ``program_dispatch_split`` line."""
    records, counters = program_spans.ring()
    units = _working(records)
    launches = [u["inner"]["launch"] * 1e3 for u in units if "launch" in u["inner"]]
    if not launches:
        return None
    _log_split(units, records, counters)
    return stats.percentile(launches, 50)


def operand_leaves():
    """The leaves every call of the newest scheduler's programs is handed:
    the gauge ``program_operand_leaves``."""
    _, counters = program_spans.ring()
    return counters.get("program_operand_leaves")


def device_dry_pct():
    """100 x the seconds of the ``device_dry`` records (the lower bound)
    inside the steady non-idle ticks over those ticks' seconds. 0 where the
    program looks and never found the device dry."""
    records, counters = program_spans.ring()
    units = _working(records)
    if "ticks_device_dry" not in counters or not units:
        return None
    least = sum(_dry_bounds(u)[0] for u in units)
    return 100.0 * least / sum(u["tick"].end - u["tick"].start for u in units)


def units_stalled(name):
    """The counter ``units_stalled_<name>`` (``tick``, ``train_batch``),
    which the recorder shows as 0 from the first unit of that name. Logs
    the ``stall`` records the ring holds, and the ring's state, where the
    unit is a training step (a serve cell's are on its split line)."""
    records, counters = program_spans.ring()
    count = counters.get("units_stalled_" + name)
    if count is not None and name != "tick":
        harness.log(program_stalls={"units_stalled": count,
                                    "stall_us": counters.get("stall_us_" + name, 0),
                                    "stalls": stalls(records), "ring": ring_state()})
    return count
