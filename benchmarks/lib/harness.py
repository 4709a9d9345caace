"""What every cell needs and no cell owns: finding a cell's files by the
names in ``BENCHMARK.json``, the device check, set-up accounting, the
compile counter, the profiler window, the per-layer readers and the last
line of the output. ``benchmarks/run.py`` is the command; the tests call
:func:`run_cell` directly, with ``require_tpu=False``.
"""

import contextlib
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

from benchmarks.lib import peaks as peaks_table
from benchmarks.lib import trace as trace_lib

BENCH_DIR = "benchmarks"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class BenchmarkError(RuntimeError):
    """The run cannot give a result; the command exits non-zero."""


def log(**fields):
    """An earlier line of the output: one JSON object, never the last."""
    print(json.dumps(fields), flush=True)


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(root, *parts):
    """A module of the benchmark loaded by its path under ``root``, so that
    a copy of the benchmark with files added runs its own files."""
    path = os.path.join(root, *parts)
    name = "_bench_" + "_".join(parts).replace(".py", "").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    def __init__(self, root, manifest, workload):
        entries = [w for w in manifest["workloads"] if w["name"] == workload]
        if not entries:
            raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json")
        self.root = root
        self.entry = entries[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        files = {c["name"]: c["file"] for c in manifest["configs"]}
        self.config = load_json(root, files[self.entry["config"]])
        self.traffic = load_json(root, BENCH_DIR, "traffic", self.entry["traffic"] + ".json")
        here = lambda m: "workloads" not in m or workload in m["workloads"]  # noqa: E731
        self.end_to_end = [m for m in manifest["end_to_end"] if here(m)]
        self.per_layer = [m for m in manifest["per_layer"] if here(m)]

    @functools.cached_property
    def family(self):
        return load_module(self.root, BENCH_DIR, "families", self.config["family"] + ".py")

    @functools.cached_property
    def runner(self):
        return load_module(self.root, BENCH_DIR, "runners", self.traffic["kind"] + ".py")


class Setup:
    """Set-up time from the start of the process, divided into phases."""

    def __init__(self, t0):
        self.t0 = self.last = t0
        self.phases = []

    def mark(self, name):
        now = time.time()
        self.phases.append([name, now - self.last])
        self.last = now

    def close(self):
        """The measured window opens now: the set-up time is fixed."""
        self.seconds = time.time() - self.t0
        log(setup_s=self.seconds, phases=self.phases)
        return self.seconds


@contextlib.contextmanager
def compiles():
    """Collects (program, seconds) of every backend compilation in the
    block, persistent-cache hits included (as ``chip_smoke._compiles``)."""
    import jax.monitoring

    seen = []

    def listener(event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append((fun_name, duration))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@contextlib.contextmanager
def quiet_host():
    """No garbage collection inside a measured window."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def devices_for(chips, require_tpu):
    """The first ``chips`` devices; an error where JAX has no TPU or too few."""
    import jax

    found = jax.devices()
    if require_tpu and found[0].platform != "tpu":
        raise BenchmarkError(f"JAX found no TPU (platform {found[0].platform!r}); "
                             f"nothing is measured on another platform")
    if len(found) < chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s), JAX reports {len(found)}")
    return found[:chips]


def memory_peak_bytes(devices):
    """Largest ``peak_bytes_in_use`` over the devices; None where the
    backend does not report it (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Tracer:
    """The profiler around a slice of the window, and the runner's spans on
    its clock. With tracing off every method costs nothing."""

    def __init__(self, enabled, out_dir):
        self.enabled = enabled
        self.dir = out_dir
        self._window = None
        self.traced = False     # a window shorter than the traced slice never starts it

    def span(self, name):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(trace_lib.SPAN_PREFIX + name)

    def start(self):
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the runner's own spans are enough
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.traced = True
        self._window = self.span(trace_lib.WINDOW_SPAN)
        self._window.__enter__()

    def stop(self):
        """Call with the device known to be done."""
        if self._window is None:
            return
        import jax
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()

    def reduce(self, label=trace_lib.op_family):
        if not self.traced:
            return None
        reduced = trace_lib.reduce(trace_lib.load(trace_lib.find_xplane(self.dir)), label)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


class Env:
    """What the harness hands a runner."""

    def __init__(self, seed, seconds, trace, setup, devices, tracer):
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.setup = setup
        self.devices = devices
        self.tracer = tracer

    @property
    def seed31(self):
        """``--seed`` folded under 2**31, for the places that take a signed
        32-bit seed (a JAX PRNG key, the engine's config)."""
        return self.seed % (2 ** 31 - 1)


def read_layer_metrics(cell, ctx):
    """Each per-layer metric of the cell through its own reader,
    ``layer_metrics/<name>.json`` (a reducer of ``lib/reducers.py`` with its
    arguments) or ``<name>.py`` (``read(ctx)``). A reader that finds
    nothing returns None and the metric is left out."""
    from benchmarks.lib import reducers

    out = {}
    for metric in cell.per_layer:
        base = os.path.join(cell.root, BENCH_DIR, "layer_metrics", metric["name"])
        if os.path.exists(base + ".py"):
            value = load_module(cell.root, BENCH_DIR, "layer_metrics",
                                metric["name"] + ".py").read(ctx)
        else:
            spec = load_json(cell.root, BENCH_DIR, "layer_metrics", metric["name"] + ".json")
            value = getattr(reducers, spec["reducer"])(ctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def _no_device_operation(counters):
    """Why a traced slice held nothing, as far as the runner's counters say:
    a serving runner traces the END of its window, which a cell whose
    backlog a fast server has emptied spends idle."""
    said = "the traced window holds no device operation"
    queued = counters.get("queue_at_close")
    if queued is None:
        return said
    if queued == 0:
        return said + (": the runner's queue was empty at the window's close, so a cell that "
                       "is a backlog had run out of work before the traced slice (its mix "
                       "offers this server too little)")
    return said + f", though the runner's queue held {queued} requests at the window's close"


def run_cell(root, manifest, workload, seed, seconds, trace, t0=None, require_tpu=True):
    """Run one cell once and return the object of the last line."""
    setup = Setup(t0 if t0 is not None else time.time())
    cell = Cell(root, manifest, workload)
    devices = devices_for(cell.chips, require_tpu)
    kind = devices[0].device_kind
    peaks = peaks_table.peaks_for(kind) if devices[0].platform == "tpu" else None
    tracer = Tracer(bool(trace), os.path.join(root, ".bench_out", "trace", workload))
    env = Env(seed, seconds, trace, setup, devices, tracer)

    result = cell.runner.run(cell, env)

    # the peak as the runner read it, where only the system under test had run
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": device}
    if not env.trace:
        values = dict(result["end_to_end"], setup_s=setup.seconds)
        line["metrics"] = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                           for m in cell.end_to_end}
        return line
    reduced = env.tracer.reduce(getattr(cell.family, "op_label", trace_lib.op_family))
    if reduced is None and require_tpu:
        raise BenchmarkError(_no_device_operation(result.get("counters", {})))
    ctx = {"cell": cell, "chips": cell.chips, "peaks": peaks, "trace": reduced,
           "spans": result.get("spans", {}), "counters": result.get("counters", {})}
    line["metrics"] = read_layer_metrics(cell, ctx)
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": trace_lib.top(reduced["family_seconds"]),
                             "idle_gaps": trace_lib.top(reduced["idle_gaps"])}
    return line


def main(argv, root, t0):
    import argparse

    parser = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json once.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        line = run_cell(root, load_json(root, "BENCHMARK.json"), args.workload, args.seed,
                        args.seconds, args.trace, t0=t0)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0
