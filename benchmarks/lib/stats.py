"""Metric arithmetic on plain lists: percentiles and the spread the bounds
are sized from. No numpy, so the same numbers come out everywhere."""

import math
import statistics


def percentile(values, p):
    """The p-th percentile (0-100) by linear interpolation between closest
    ranks, as ``numpy.percentile`` gives it. None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    rank = (len(vals) - 1) * p / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them:
    the measure the benchmark's bounds are sized from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
