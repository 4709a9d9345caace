"""The general readers a per-layer metric's file (``layer_metrics/<name>.json``)
can name. Each takes the run's context (``spans``: lists of durations by
name; ``counters``; ``trace``: the reduced profiler window, or None) and
its own arguments, and returns a number, or None where there is nothing
to read."""

import re

from benchmarks.lib import stats


def counter(ctx, key, scale=1.0):
    value = ctx["counters"].get(key)
    return None if value is None else value * scale


def counter_share_pct(ctx, part, whole):
    """100 x counters[part] / sum of counters[whole...]."""
    total = sum(ctx["counters"].get(k, 0) for k in whole)
    if part not in ctx["counters"] or not total:
        return None
    return 100.0 * ctx["counters"][part] / total


def span_percentile(ctx, span, p):
    return stats.percentile(ctx["spans"].get(span, []), p)


def device_idle_pct(ctx):
    """1 - union of device-operation intervals over the traced window,
    first device."""
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s_first"] / tr["window_s"])


def op_seconds(ctx, match):
    """Self time on the first device of the operation families (as the
    trace reducer labels them) that match the regular expression."""
    tr = ctx["trace"]
    if tr is None:
        return None
    pattern = re.compile(match)
    return sum(secs for name, secs in tr["family_seconds"].items() if pattern.search(name))


def op_time_pct(ctx, match):
    """Share of device-busy time in operation families matching ``match``."""
    secs = op_seconds(ctx, match)
    if secs is None or not ctx["trace"]["busy_s_first"]:
        return None
    return 100.0 * secs / ctx["trace"]["busy_s_first"]
