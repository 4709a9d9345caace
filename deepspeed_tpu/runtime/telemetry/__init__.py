"""Unified runtime telemetry (graft-trace, ISSUE 13).

* :mod:`.metrics` — the mergeable fixed-bucket histogram;
* :mod:`.sink` — schema-versioned rank-0 JSONL event log;
* :mod:`.core` — :class:`RuntimeTelemetry`, the engine-facing facade
  (event bus + run header + window flush + drift).

The spans and counters themselves live in the process's one recorder,
``deepspeed_tpu.utils.trace``; this package consumes it.

Reader/report side: ``tools/trace_report.py``.
"""

from deepspeed_tpu.runtime.telemetry.core import (RuntimeTelemetry, config_signature,
                                                  drift_ratios, measured_memory,
                                                  TELEMETRY_FILE)
from deepspeed_tpu.runtime.telemetry.metrics import DEFAULT_LATENCY_BOUNDS, Histogram
from deepspeed_tpu.runtime.telemetry.sink import (TELEMETRY_SCHEMA_VERSION, JsonlSink,
                                                  iter_events, read_events)

__all__ = [
    "RuntimeTelemetry", "config_signature", "drift_ratios", "measured_memory",
    "TELEMETRY_FILE",
    "Histogram", "DEFAULT_LATENCY_BOUNDS",
    "TELEMETRY_SCHEMA_VERSION", "JsonlSink", "iter_events", "read_events",
]
