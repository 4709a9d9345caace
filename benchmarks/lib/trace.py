"""From a profiler trace to numbers. ``load`` reads an ``.xplane.pb`` with
nothing but JAX into plain lists; everything after it is arithmetic on
intervals, so it can be checked without a trace.

On a TPU each chip is a plane ``/device:TPU:<n>``, whose line ``XLA Ops``
holds one event per executed HLO operation (a ``while`` holds its body's
operations nested inside it). The runner's spans are ``TraceAnnotation``
events named ``bench:<span>`` on the host plane, on the same clock.
"""

import bisect
import glob
import os
import re

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "window"
NO_SPAN = "_no_benchmark_span_"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    """``{"devices": {plane: [(name, start_s, dur_s), ...]}, "spans":
    [(name, start_s, dur_s), ...], "op_stats": {name: {stat: value}}}``.
    Device events are those of the ``XLA Ops`` line; ``op_stats`` keeps the
    first event's stats for every distinct operation name."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, op_stats = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = devices.setdefault(plane.name, [])
                for ev in line.events:
                    events.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
                    if ev.name not in op_stats:
                        op_stats[ev.name] = {k: v for k, v in ev.stats
                                             if isinstance(v, (str, int, float))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9,
                                      ev.duration_ns * 1e-9))
    return {"devices": {k: devices[k] for k in sorted(devices)}, "spans": sorted(
        spans, key=lambda s: s[1]), "op_stats": op_stats}


def clip(events, lo, hi):
    """Events cut to [lo, hi]; those outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(intervals):
    """Merged, sorted (start, end) pairs of possibly overlapping ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(events):
    return sum(e - s for s, e in union((s, s + d) for _, s, d in events))


def self_times(events):
    """Seconds by operation name with nested time given to the innermost
    event, so a ``while`` is not counted again for its body."""
    out = {}
    stack = []  # (name, end, self-time accumulator index)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(dur, stack[-1][1] - start)
        out[name] = out.get(name, 0.0) + dur
        stack.append((name, start + dur))
    return out


_HLO_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(text):
    """The instruction's name from an event's text. The TPU's ``XLA Ops``
    events carry the whole HLO instruction, ``%fusion.12 = bf16[..] fusion(..)``."""
    return _HLO_NAME.match(text).group(1)


def is_custom_call(text):
    """Whether the event is a custom call: on a TPU, a Mosaic (Pallas) kernel."""
    return " custom-call(" in text


def op_family(text, stats=None):
    """``%fusion.123 = ...`` -> ``fusion``: the instruction's name without
    its number. A family's own ``op_label(text, stats)`` may name its
    kernels instead."""
    return re.sub(r"(\.\d+|\.clone|\.remat\d*)+$", "", op_name(text)) or text


def by_family(times, op_stats, label=op_family):
    out = {}
    for name, secs in times.items():
        family = label(name, op_stats.get(name, {}))
        out[family] = out.get(family, 0.0) + secs
    return out


def gaps_by_span(busy, spans, lo, hi):
    """Idle seconds inside [lo, hi] by the runner span the host was in.
    ``busy`` is a merged interval list; ``spans`` are (name, start, dur).
    Idle time under no span goes to ``_no_benchmark_span_``.

    Spans nest, and every span over a gap is given the gap (an outer one
    as its inner ones), so the gaps are walked once and each span looks its
    own up in them: the gaps are disjoint and in time order, a span meets a
    run of them, cuts at most the first and the last of the run and holds
    the ones between whole. Those it reads off the running sum of the gaps'
    lengths; ``whole`` keeps, as a difference over the gaps, how many spans
    hold each whole, and ``cut`` the seconds of each under spans that cut
    it, for what is left under no span."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    starts = [g0 for g0, _ in gaps]
    ends = [g1 for _, g1 in gaps]
    before = [0.0] * (len(gaps) + 1)         # idle seconds in the gaps ahead of each
    for i, (g0, g1) in enumerate(gaps):
        before[i + 1] = before[i] + (g1 - g0)
    cut = [0.0] * len(gaps)
    whole = [0] * (len(gaps) + 1)
    out = {}
    for name, start, dur in spans:
        end = start + dur
        if name == WINDOW_SPAN or end <= start:
            continue
        first = bisect.bisect_right(ends, start)         # the first gap that ends after the span starts
        last = bisect.bisect_left(starts, end) - 1       # the last that starts before it ends
        if first > last:
            continue
        over = 0.0
        for i in ((first,) if first == last else (first, last)):
            part = min(ends[i], end) - max(starts[i], start)
            cut[i] += part
            over += part
        if last - first > 1:
            over += before[last] - before[first + 1]
            whole[first + 1] += 1
            whole[last] -= 1
        out[name] = out.get(name, 0.0) + over
    held = 0
    for i, (g0, g1) in enumerate(gaps):
        held += whole[i]
        left = (g1 - g0) - cut[i] - held * (g1 - g0)
        if left > 1e-12:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + left
    return out


def reduce(loaded, label=op_family):
    """What the runners and the per-layer readers use: the traced window
    (the ``bench:window`` span), busy seconds averaged over the devices,
    and for the first device self time by operation family and idle gaps by
    span. None when the trace holds no device event inside the window."""
    window = [s for s in loaded["spans"] if s[0] == WINDOW_SPAN]
    if not window or not loaded["devices"]:
        return None
    lo, hi = window[0][1], window[0][1] + window[0][2]
    per_device = {name: clip(evs, lo, hi) for name, evs in loaded["devices"].items()}
    busy = [busy_seconds(evs) for evs in per_device.values()]
    if not any(busy):
        return None
    first = next(iter(per_device.values()))
    times = self_times(first)
    merged = union((s, s + d) for _, s, d in first)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "busy_s_first": busy_seconds(first),
        "op_seconds": times,
        "family_seconds": by_family(times, loaded["op_stats"], label),
        "idle_gaps": gaps_by_span(merged, loaded["spans"], lo, hi),
        "op_stats": loaded["op_stats"],
    }


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
