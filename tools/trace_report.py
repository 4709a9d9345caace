"""trace_report: turn a graft-trace telemetry JSONL into human/tool views.

Two modes over the run log ``runtime/telemetry`` writes:

* default — export the step-span timeline as **Chrome trace-event JSON**
  (the ``chrome://tracing`` / Perfetto "JSON Array with metadata" format:
  ``{"traceEvents": [...]}`` of complete ``"ph": "X"`` events). Span
  nesting falls out of timestamp containment on one tid; ``step_window``
  aggregates ride along as counter (``"ph": "C"``) series so achieved
  step time is visible next to the phases. The spans of the start
  (``initialize``, ``scheduler_init``, ``warmup``) and JAX's compile
  events, filed by the recorder under the span that caused them, are spans
  like the rest; every ``recompile`` of the run, a compile under a tick or
  a step after the first of its kind, is also listed on stderr with its
  tick or step, the phase and the function.
* ``--drift`` — summarize the predicted-vs-measured loop: the run
  header's static price (flops_proxy, liveness peak/transient bytes)
  against each window's measured median step time and memory peaks,
  printed as a table plus one JSON summary line, AND written as a
  machine-readable sidecar (default ``<run_dir>/drift.json``, ``--out``
  overrides, ``-`` suppresses) — the per-window predicted/measured/ratio
  rows ``tools/graft_calibrate.py`` fits calibration coefficients from.
  This is the chip-window view that banks *model error*, not just
  milliseconds.

This tool only READS json — no jax import, safe anywhere (including
while a run is still writing; torn tail lines are skipped).

Usage:
  python tools/trace_report.py <run_dir_or_jsonl> [--out trace.json]
  python tools/trace_report.py <run_dir_or_jsonl> --drift
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from deepspeed_tpu.runtime.telemetry.core import TELEMETRY_FILE, drift_ratios  # noqa: E402
from deepspeed_tpu.runtime.telemetry.sink import iter_events  # noqa: E402


def resolve_jsonl(path: str) -> str:
    """Accept the run dir or the jsonl file itself."""
    if os.path.isdir(path):
        candidate = os.path.join(path, TELEMETRY_FILE)
        if not os.path.exists(candidate):
            raise FileNotFoundError(f"no {TELEMETRY_FILE} under {path}")
        return candidate
    return path


def chrome_trace(events) -> dict:
    """Chrome trace-event JSON from the run's span + window events."""
    trace = []
    pid = 0
    run = {}
    for rec in events:
        kind = rec.get("event")
        if kind == "run_start":
            run = rec.get("run") or {}
            pid = run.get("pid", 0) or 0
            trace.append({"name": "process_name", "ph": "M", "pid": pid,
                          "args": {"name": f"deepspeed_tpu {run.get('model', '')} "
                                           f"[{run.get('config_sig', '')}]".strip()}})
            trace.append({"name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
                          "args": {"name": "step spans"}})
        elif kind == "spans":
            for s in rec.get("spans", ()):
                args = {"path": s.get("path", ""), "depth": s.get("depth", 0)}
                if "kind" in s:     # a tick's kind; a compile record's function
                    args["kind"] = s["kind"]
                trace.append({"name": s.get("name", "?"), "ph": "X", "pid": pid,
                              "tid": 1,
                              "ts": float(s.get("ts", 0.0)) * 1e6,
                              "dur": float(s.get("dur_s", 0.0)) * 1e6,
                              "args": args})
        elif kind == "step_window":
            step_phase = (rec.get("phases") or {}).get("step") or {}
            p50 = step_phase.get("p50")
            if p50 is not None:
                trace.append({"name": "step_p50_ms", "ph": "C", "pid": pid, "tid": 0,
                              "ts": float(rec.get("t", 0.0)) * 1e6,
                              "args": {"ms": p50 * 1e3}})
        elif kind in ("checkpoint", "xla_trace", "preempt_checkpoint"):
            trace.append({"name": kind, "ph": "i", "pid": pid, "tid": 1, "s": "g",
                          "ts": float(rec.get("t", 0.0)) * 1e6,
                          "args": {k: v for k, v in rec.items()
                                   if k not in ("event", "t")}})
    return {"traceEvents": trace, "displayTimeUnit": "ms",
            "otherData": {"run": run}}


def recompiles(events) -> list:
    """The run's ``recompile`` records, oldest first: the unit (tick or
    step) each fell in and its kind, the phase, the function, the seconds."""
    spans = [s for rec in events if rec.get("event") == "spans" for s in rec.get("spans", ())]
    kinds = {s.get("uid"): s.get("kind") for s in spans if not s.get("path")}
    return [{"unit": s.get("uid"), "kind": kinds.get(s.get("uid")),
             "phase": (s.get("path") or "").rsplit("/", 1)[-1] or None,
             "function": s.get("kind"), "dur_s": s.get("dur_s")}
            for s in spans if s.get("name") == "recompile"]


def drift_report(events) -> dict:
    """Windows + overall summary of predicted-vs-measured."""
    price, run, windows = None, {}, []
    for rec in events:
        if rec.get("event") == "run_start":
            run = rec.get("run") or {}
            price = rec.get("static_price")
        elif rec.get("event") == "drift":
            windows.append(rec)
    # overall: time-weighted across windows (median of window medians is
    # fine at this granularity; windows are equal step counts by cadence)
    meds = [w["median_step_s"] for w in windows if w.get("median_step_s")]
    med = sorted(meds)[len(meds) // 2] if meds else None
    measured = windows[-1].get("measured") if windows else {}
    return {"run": run, "predicted": price, "windows": windows,
            "median_step_s": med,
            "ratios": drift_ratios(price, med, measured)}


def print_drift(report) -> None:
    price = report.get("predicted") or {}
    run = report.get("run") or {}
    print(f"# drift report: model={run.get('model')} config={run.get('config_sig')} "
          f"backend={run.get('backend')}")
    if price.get("error"):
        # pricing failed at header time (the engine degrades to an
        # {"error": ...} stamp) — report that instead of crashing the
        # one tool meant to inspect such runs
        print(f"# predicted: unavailable ({price['error']})")
    elif price:
        print(f"# predicted: flops_proxy={_count(price.get('flops_proxy'))} "
              f"peak={_mib(price.get('peak_bytes'))} "
              f"transient={_mib(price.get('peak_transient_bytes'))} "
              f"wire={_mib(price.get('bytes_moved'))}")
    hdr = f"{'step':>8} {'steps':>6} {'med_ms':>10} {'TFLOPS':>9}  memory ratios"
    print(hdr)
    for w in report["windows"]:
        med = w.get("median_step_s")
        r = w.get("ratios") or {}
        ratio_bits = " ".join(f"{k}={v:.3f}" for k, v in r.items()
                              if k != "achieved_tflops")
        print(f"{w.get('step', '?'):>8} {w.get('window_steps', '?'):>6} "
              f"{(med or 0) * 1e3:>10.3f} {r.get('achieved_tflops', 0):>9.4f}  "
              f"{ratio_bits}")
    print(json.dumps({"summary": {"median_step_s": report["median_step_s"],
                                  "ratios": report["ratios"]}}))


def _mib(n):
    return f"{n / 2**20:.1f}MiB" if isinstance(n, (int, float)) else "n/a"


def _count(n):
    return f"{n:,}" if isinstance(n, (int, float)) else "n/a"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trace_report", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("path", help="telemetry run dir or telemetry.jsonl")
    ap.add_argument("--out", default=None,
                    help="output path: the Chrome trace JSON (default "
                         "<run_dir>/chrome_trace.json, '-' for stdout) or, "
                         "with --drift, the JSON sidecar (default "
                         "<run_dir>/drift.json, '-' to suppress)")
    ap.add_argument("--drift", action="store_true",
                    help="print the predicted-vs-measured drift table and "
                         "write the machine-readable drift.json sidecar instead")
    args = ap.parse_args(argv)

    jsonl = resolve_jsonl(args.path)
    events = list(iter_events(jsonl))
    if not events:
        print(f"trace_report: no events in {jsonl}", file=sys.stderr)
        return 1

    if args.drift:
        report = drift_report(events)
        print_drift(report)
        # the sidecar keeps the drift rows machine-readable instead of
        # dying in stdout — graft_calibrate consumes it as a fit source
        out = args.out or os.path.join(os.path.dirname(jsonl), "drift.json")
        if out != "-":
            with open(out, "w") as fh:
                json.dump(report, fh, indent=2)
                fh.write("\n")
            print(f"drift sidecar: {out} ({len(report['windows'])} windows)")
        return 0

    for r in recompiles(events):
        print(f"trace_report: recompile in unit {r['unit']} ({r['kind'] or 'step'}), phase "
              f"{r['phase']}: {r['function']} took {(r['dur_s'] or 0.0) * 1e3:.1f} ms",
              file=sys.stderr)
    trace = chrome_trace(events)
    if not trace["traceEvents"]:
        print(f"trace_report: no span events in {jsonl} (telemetry.span_events "
              f"off, or the run never reached a flush boundary)", file=sys.stderr)
        return 1
    out = args.out or os.path.join(os.path.dirname(jsonl), "chrome_trace.json")
    if out == "-":
        json.dump(trace, sys.stdout)
        print()
    else:
        with open(out, "w") as fh:
            json.dump(trace, fh)
        print(f"chrome trace: {out} ({len(trace['traceEvents'])} events) — "
              f"load in chrome://tracing or ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
