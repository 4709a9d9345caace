"""RuntimeTelemetry: the engine-facing consumer of the recorder, plus the sink.

Three pillars (ISSUE 13):

1. **Structured event log + metrics** — a schema-versioned JSONL file
   per run (``sink.py``) whose ``run_start`` header stamps the config
   signature, jax/jaxlib versions, mesh axes and the step program's
   *static price* (``analysis.static_price_from_programs``: flops_proxy,
   liveness peak/transient bytes, analytic wire bytes). Monitor events
   ride a tiny bus: ``MonitorMaster`` is just one subscriber, so
   TB/W&B/CSV behavior is unchanged while every published event also
   lands durably in the JSONL.
2. **Step-span timeline** — the process's recorder
   (``deepspeed_tpu.utils.trace``) holds the host-phase spans, always;
   every ``flush_every`` steps this consumer reads the records that carry
   its ``source`` and writes one ``spans`` event (raw timeline) and one
   ``step_window`` event (per-phase p50/p99 aggregates).
   ``tools/trace_report.py`` turns the timeline into Chrome trace-event
   JSON. The engine's ``trace_profiler`` config block opens a
   ``jax.profiler`` device-trace window, in which the same spans lie
   under ``ds:``.
3. **Drift** — each window closes with a ``drift`` event: achieved
   TFLOPS (predicted ``flops_proxy`` ÷ measured median step time) and
   predicted-vs-measured memory ratios (device ``memory_stats`` peaks
   where the backend reports them — TPU; host peak RSS as the loose
   CPU-backend proxy, explicitly labeled). ``drift_summary()`` gives the
   whole run's.

The recorder instruments only host code around the dispatched step —
the traced program is bit-identical with telemetry on (gated by the
``train_batch_telemetry`` scenario / rule R015 and the tier-1 overhead
test).
"""

import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from deepspeed_tpu.runtime.telemetry.metrics import Histogram
from deepspeed_tpu.runtime.telemetry.sink import (TELEMETRY_SCHEMA_VERSION, JsonlSink)
from deepspeed_tpu.utils import trace
from deepspeed_tpu.utils.logging import logger

__all__ = ["RuntimeTelemetry", "config_signature", "measured_memory", "TELEMETRY_FILE"]

TELEMETRY_FILE = "telemetry.jsonl"


def config_signature(raw_dict: Dict) -> str:
    """Stable short signature of the user config (run-header provenance)."""
    try:
        blob = json.dumps(raw_dict, sort_keys=True, default=str)
    except (TypeError, ValueError):
        blob = repr(raw_dict)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def measured_memory() -> Dict[str, int]:
    """Runtime memory observations, backend-dependent: device
    ``memory_stats`` peaks where the backend reports them (TPU/GPU), and
    host peak RSS (ru_maxrss) always — on the CPU backend the device IS
    the host, so RSS is the (loose, process-lifetime) measured bound the
    drift ratio uses there."""
    out: Dict[str, int] = {}
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        for src, dst in (("peak_bytes_in_use", "device_peak_bytes"),
                         ("bytes_in_use", "device_bytes_in_use")):
            if src in stats:
                out[dst] = int(stats[src])
    except Exception:  # noqa: BLE001 — observability never raises
        pass
    try:
        import resource
        # linux reports KiB
        out["host_peak_rss_bytes"] = int(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    except Exception:  # noqa: BLE001
        pass
    return out


def drift_ratios(price: Optional[Dict], median_step_s: Optional[float],
                 measured: Optional[Dict] = None) -> Dict[str, Any]:
    """The predicted-vs-measured core, shared by the window flush,
    ``drift_summary`` and ``tools/trace_report.py --drift``."""
    out: Dict[str, Any] = {}
    price = price or {}
    measured = measured if measured is not None else {}
    flops = price.get("flops_proxy")
    if flops and median_step_s:
        # predicted FLOPs over measured seconds — the flops half of the
        # drift pair (a chip window compares this against its banked MFU)
        out["achieved_tflops"] = flops / median_step_s / 1e12
    peak = price.get("peak_bytes")
    transient = price.get("peak_transient_bytes")
    dev_peak = measured.get("device_peak_bytes")
    if dev_peak and peak:
        out["device_peak_ratio"] = dev_peak / peak
    if dev_peak and transient:
        out["device_peak_vs_predicted_transient"] = dev_peak / transient
    rss = measured.get("host_peak_rss_bytes")
    if rss and peak and dev_peak is None:
        # CPU backend: host RSS is the only measured bound (includes the
        # interpreter + compile peaks — an upper proxy, labeled as such)
        out["host_rss_vs_predicted_peak"] = rss / peak
    return out


class RuntimeTelemetry:
    """Facade the engine owns. Its spans always go to the process's
    recorder, under this object's ``source``. Disabled
    (`cfg.enabled=False`) it is otherwise a pure event bus:
    ``publish_events`` still fans out to subscribers (MonitorMaster), the
    window flush and the sink are no-ops."""

    def __init__(self, cfg=None, flush_every: int = 10, rank: int = 0,
                 run_info_fn: Optional[Callable[[], Dict]] = None, label: str = "run"):
        self.cfg = cfg
        self.enabled = bool(cfg is not None and getattr(cfg, "enabled", False))
        self.rank = int(rank)
        self.flush_every = max(int(getattr(cfg, "flush_interval_steps", 0) or 0)
                               or int(flush_every), 1)
        self._run_info_fn = run_info_fn
        self.recorder = trace.recorder()
        self.source = trace.new_source(label)
        self._cursor = self.recorder.last_seq    # the window flush reads on from here
        self._epoch = time.time() - time.perf_counter()  # span starts -> JSONL ``ts``
        self.max_buffered = int(getattr(cfg, "max_buffered_spans", 4096) or 4096)
        self._window_step = Histogram()
        self.run_dir: Optional[str] = None
        self.sink = JsonlSink(None)
        if self.enabled:
            base = getattr(cfg, "output_path", None) or "./telemetry_logs"
            self.run_dir = os.path.join(base, getattr(cfg, "job_name", "run"))
            self.sink = JsonlSink(os.path.join(self.run_dir, TELEMETRY_FILE),
                                  rank=self.rank)
        self._subscribers: List[Callable] = []
        self._header_written = False
        self.static_price: Optional[Dict] = None
        self._step_t0: Optional[float] = None
        self._window_steps = 0
        self._last_step = 0
        self._phase_totals: Dict[str, Histogram] = {}
        self._step_hist_total = Histogram()

    # -- bus -----------------------------------------------------------
    def subscribe(self, fn: Callable) -> None:
        """Register a monitor-event consumer (``fn(event_list)``);
        MonitorMaster.write_events is the canonical subscriber."""
        self._subscribers.append(fn)

    @property
    def has_consumers(self) -> bool:
        """Someone will actually see a published event batch: a subscriber
        (MonitorMaster, rank-0 only) or the live JSONL sink (rank-gated).
        On non-zero ranks with telemetry enabled this is False — the engine
        must not pay for the MoE diagnostic forward to feed nobody."""
        return bool(self._subscribers) or (self.enabled and self.sink.active)

    def publish_events(self, events: List[Tuple], step: Optional[int] = None) -> None:
        """Fan one ``(tag, value, step)`` event batch out to every
        subscriber AND (when enabled) the JSONL log."""
        if not events:
            return
        for fn in self._subscribers:
            try:
                fn(events)
            except Exception as e:  # noqa: BLE001 — a sink must not kill a step
                logger.warning(f"telemetry subscriber {fn} failed: {e}")
        if self.enabled:
            self.sink.write({"event": "monitor", "step": step,
                             "events": [[t, float(v), int(s)] for t, v, s in events]})

    # -- run header ----------------------------------------------------
    @property
    def wants_run_header(self) -> bool:
        return self.enabled and not self._header_written and self.sink.active

    def write_run_header(self, run_info: Optional[Dict] = None,
                         static_price: Optional[Dict] = None) -> None:
        if not self.enabled or self._header_written:
            return
        self._header_written = True
        if static_price is not None:
            self.static_price = static_price
        info = dict(run_info or {})
        if not info and self._run_info_fn is not None:
            try:
                info = self._run_info_fn()
            except Exception as e:  # noqa: BLE001
                info = {"run_info_error": str(e)}
        self.sink.write({"event": "run_start",
                         "schema": TELEMETRY_SCHEMA_VERSION,
                         "run": info,
                         "static_price": self.static_price}, flush=True)

    # -- spans / steps -------------------------------------------------
    def span(self, name: str, uid: Optional[int] = None, marks: int = 0):
        return self.recorder.span(name, uid, self.source, marks)

    @property
    def last_span(self) -> Optional[str]:
        return self.recorder.last_span

    def begin_step(self, step: int) -> None:
        if not self.enabled:
            return
        self._step_t0 = time.perf_counter()

    def end_step(self, step: int, n_steps: int = 1) -> None:
        """Close the per-step record; at ``flush_every`` cadence emit the
        window's spans + aggregates + drift. ``n_steps`` > 1 for a fused
        ``train_batches`` stack (one dispatch, n optimizer steps — the
        per-step time is the stack time ÷ n)."""
        if not self.enabled or self._step_t0 is None:
            return
        wall = time.perf_counter() - self._step_t0
        self._step_t0 = None
        per_step = wall / max(n_steps, 1)
        for _ in range(n_steps):  # fused stacks: n per-step samples at stack/n each
            self._window_step.record(per_step)
            self._step_hist_total.record(per_step)
        self._window_steps += n_steps
        self._last_step = step
        if step % self.flush_every == 0 or self._window_steps >= self.flush_every:
            self.flush_window(step)

    def _drain(self) -> Tuple[List[Dict], Dict[str, Histogram], int]:
        """This source's records since the last flush as (span events for
        the JSONL, per-phase window histograms, records lost): at most
        ``max_buffered`` events a window, the histograms from every record
        the ring still held."""
        records, dropped = self.recorder.since(self._cursor, self.source)
        self._cursor = self.recorder.last_seq
        events: List[Dict] = []
        hists: Dict[str, Histogram] = {}
        for r in records:
            if len(events) < self.max_buffered:
                event = {"name": r.name, "path": "/".join(r.path),
                         "ts": r.start + self._epoch, "dur_s": r.dur,
                         "depth": len(r.path), "uid": r.uid}
                if r.kind is not None:      # a tick's kind; a compile record's function
                    event["kind"] = r.kind
                events.append(event)
            else:
                dropped += 1
            hists.setdefault(r.name, Histogram()).record(r.dur)
        if self._window_step.count:
            hists["step"], self._window_step = self._window_step, Histogram()
        return events, hists, dropped

    def flush_window(self, step: int) -> None:
        if not self.enabled:
            return
        events, hists, dropped = self._drain()
        self._window_steps = 0
        for name, hist in hists.items():
            total = self._phase_totals.get(name)
            if total is None:
                self._phase_totals[name] = hist
            else:
                total.merge(hist)
        if not self.sink.active:
            return
        if events and getattr(self.cfg, "span_events", True):
            self.sink.write({"event": "spans", "step": step, "dropped": dropped,
                             "spans": events})
        if hists:  # an empty window (explicit flush, no steps) emits nothing
            window = {"event": "step_window", "step": step,
                      "phases": {name: h.snapshot() for name, h in hists.items()}}
            if self.recorder.counters:   # process totals, not this source's alone
                # what the ring has pushed out by now: a reader of the ring (a
                # median, a stall's children) saw no more than it still held
                self.recorder.gauge("ring_records_dropped", self.recorder.dropped)
                window["metrics"] = {"counters": dict(self.recorder.counters)}
            self.sink.write(window)
            step_hist = hists.get("step")
            med = step_hist.percentile(50) if step_hist else None
            measured = measured_memory()
            self.sink.write({"event": "drift", "step": step,
                             "window_steps": step_hist.count if step_hist else 0,
                             "median_step_s": med,
                             "predicted": self.static_price,
                             "measured": measured,
                             "ratios": drift_ratios(self.static_price, med, measured)})
        self.sink.flush()

    # -- raw events ----------------------------------------------------
    def emit(self, kind: str, /, flush: bool = True, **fields) -> None:
        """Write one structured event (checkpoint publish, xla trace
        window, resilience fallback, ...). No-op when disabled.

        ``kind`` is positional-only so an event may carry a field named
        ``kind`` (``serve_tick`` reports its tick kind that way).
        ``flush=False`` buffers the line until the next window flush —
        for per-tick cadenced events (graft-fleet ``serve_tick``) where
        an fsync per record would tax the serving hot path."""
        if not self.enabled:
            return
        rec = {"event": kind}
        rec.update(fields)
        self.sink.write(rec, flush=flush)

    # -- summaries -----------------------------------------------------
    def drift_summary(self) -> Dict[str, Any]:
        """Cumulative (whole-run) phase medians + drift ratios."""
        if self._window_steps:
            # flush the pending partial window under its real last step —
            # a step-0 label would misorder consumers keying windows by step
            self.flush_window(step=self._last_step)
        phases = {name: round((h.percentile(50) or 0.0) * 1e3, 3)
                  for name, h in self._phase_totals.items()}
        med = self._step_hist_total.percentile(50)
        out: Dict[str, Any] = {"steps": self._step_hist_total.count,
                               "phase_p50_ms": phases}
        if med is not None:
            out["median_step_s"] = med
        out["ratios"] = drift_ratios(self.static_price, med, measured_memory())
        if self.static_price:
            out["predicted"] = {k: self.static_price[k]
                                for k in ("flops_proxy", "peak_bytes",
                                          "peak_transient_bytes", "bytes_moved")
                                if k in self.static_price}
        return out

    def close(self) -> None:
        self.sink.close()
