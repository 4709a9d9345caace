"""What the six per-layer readers under ``setup_s`` share. The program's
recorder (``deepspeed_tpu/utils/trace.py``) hears JAX's compile events and
files each under the span that was open: the spans of the entry points
(``init_inference``, ``scheduler_init``, ``warmup``, ``initialize``,
``initialize_state``) and the first tick or step. Every event also adds
its microseconds to counters named by the root span it fell under, so the
totals here do not depend on what the ring still holds after the window:

* ``setup_span_us_<root>``: the entry point's own span;
* ``setup_trace_lower_us_<root>``: JAX's trace and lowering, the time the
  interpreter is held; ``setup_backend_us_<root>``: the backend compile, a
  cache retrieval when warm (``setup_cache_load_us_<root>``, inside it);
* ``setup_programs_loaded_<root>`` and ``setup_cache_hits_<root>``: backend
  compiles, and those the persistent cache answered;
* ``setup_import_us``: the package's own ``import`` records.

A jitted function traced inside another's trace is counted once, so no
sum exceeds the span it fell under. What compiled under no span of the
program (the benchmark's reference, the runner's weights) is
``compile_outside_us``, and is in none of the six. At a commit whose
program has no such counters every reader returns None.
"""

from benchmarks.lib import harness, program_spans

#: the roots whose span, less the compiles inside it, is objects, weights
#: and pools: ``setup_engine_init_s``
ENGINE_ROOTS = ("init_inference", "scheduler_init", "initialize", "initialize_state")
_PER_ROOT = ("setup_span_us", "setup_trace_lower_us", "setup_backend_us", "setup_cache_load_us",
             "setup_programs_loaded", "setup_cache_hits")
# the cache's retrieval carries no function name: it is in the roots' sums
_COMPILE_RECORDS = ("compile_trace", "compile_lower", "compile_backend")


def by_root(counters):
    """``{root: {counter: value}}`` of the counters named above; None
    where the program counts no set-up (the parent)."""
    roots = {}
    for key, value in counters.items():
        for name in _PER_ROOT:
            if key.startswith(name + "_"):
                roots.setdefault(key[len(name) + 1:], {})[name] = value
    return roots if roots or "setup_import_us" in counters else None


def _total(roots, name, only=None):
    return sum(c.get(name, 0) for root, c in roots.items() if only is None or root in only)


def _program(record):
    """The program a compile record is about: JAX names a trace by the
    function (``decode``) and the rest by ``jit(decode)``."""
    name = record.kind or "?"
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") else name


def _log_split(ctx, roots, records, counters):
    """One ``program_setup_split`` line a run: every root with its span
    and what compiled under it, the ten costliest programs the ring still
    holds, what compiled outside the program, and every recompile."""
    if ctx.get("_program_setup_split_logged"):
        return
    ctx["_program_setup_split_logged"] = True
    split = {}
    for root, c in sorted(roots.items()):
        loaded = c.get("setup_programs_loaded", 0)
        # (a first tick or step is a root with no span of the start around it)
        split[root] = {"span_s": c["setup_span_us"] / 1e6 if "setup_span_us" in c else None,
                       "trace_lower_s": c.get("setup_trace_lower_us", 0) / 1e6,
                       "backend_s": c.get("setup_backend_us", 0) / 1e6,
                       "cache_load_s": c.get("setup_cache_load_us", 0) / 1e6,
                       "programs": loaded, "cache_hits": c.get("setup_cache_hits", 0),
                       "cache_misses": loaded - c.get("setup_cache_hits", 0)}
    programs = {}
    for r in records:
        if r.name in _COMPILE_RECORDS and r.path:
            by_phase = programs.setdefault(_program(r), dict.fromkeys(_COMPILE_RECORDS, 0.0))
            by_phase[r.name] += r.end - r.start
    costliest = sorted(programs.items(), key=lambda kv: -sum(kv[1].values()))[:10]
    kinds = {(r.source, r.uid): r.kind for r in records if r.name in ("tick", "train_batch")}
    # a recompile's place in the run, to set beside ``setup_s``: seconds after
    # the package's import began (some seconds into the runner's ``imports``
    # phase), or after the oldest record where the ring has turned over
    began = min((r.start for r in records if r.name == "import"),
                default=records[0].start if records else 0.0)
    harness.log(program_setup_split={
        "roots": split,
        "programs": {name: {phase[len("compile_"):] + "_s": secs for phase, secs in phases.items()}
                     for name, phases in costliest},
        "imports": {r.kind: r.end - r.start for r in records if r.name == "import"},
        "import_s": counters.get("setup_import_us", 0) / 1e6,
        "compile_outside_s": counters.get("compile_outside_us", 0) / 1e6,
        "recompiles_in_units": counters.get("recompiles_in_units", 0),
        "recompile_s": counters.get("recompile_us", 0) / 1e6,
        "recompiles": [{"source": r.source, "unit": r.uid, "kind": kinds.get((r.source, r.uid)),
                        "phase": r.parent, "function": r.kind, "seconds": r.end - r.start,
                        "at_s": r.start - began}
                       for r in records if r.name == "recompile"]})


def read(ctx, metric):
    """One of the six, from the counters; logs the split once a run."""
    records, counters = program_spans.ring()
    roots = by_root(counters)
    if roots is None:
        return None
    _log_split(ctx, roots, records, counters)
    if metric == "setup_trace_lower_s":
        return _total(roots, "setup_trace_lower_us") / 1e6
    if metric == "setup_backend_load_s":
        return _total(roots, "setup_backend_us") / 1e6
    if metric == "setup_programs_loaded":
        return _total(roots, "setup_programs_loaded")
    if metric == "setup_cache_misses":
        return _total(roots, "setup_programs_loaded") - _total(roots, "setup_cache_hits")
    if metric == "setup_engine_init_s":
        return (_total(roots, "setup_span_us", ENGINE_ROOTS)
                - _total(roots, "setup_trace_lower_us", ENGINE_ROOTS)
                - _total(roots, "setup_backend_us", ENGINE_ROOTS)) / 1e6
    if metric == "setup_import_s":
        return counters.get("setup_import_us", 0) / 1e6
    raise KeyError(metric)
