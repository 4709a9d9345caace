"""graft-calibrate: fit the static cost model against measured telemetry.

PR 12 prices every program statically (``flops_proxy``, ``bytes_moved``,
liveness bytes — proxy units) and PR 13 measures the same programs at run
time (``drift`` events: median step seconds vs the run header's stamped
static price). Until now nobody read the drift tables back: graft-search
ranked candidates in proxy units that cannot trade compute against
memory traffic, and the predicted-vs-measured loop ended at a printout
(ROADMAP item 2). This module closes it — the reference autotuner's
*measured mode* (``/root/reference/deepspeed/autotuning/``), built on
telemetry the repo already accumulates instead of burning chip minutes:

1. **Collect** — :func:`collect_samples` walks accumulated graft-trace
   JSONL runs (or the machine-readable drift sidecars
   ``tools/trace_report.py --drift`` writes): one sample per drift
   window, ``x = (flops_proxy, bytes_moved)`` from the run header's
   static price, ``y = median_step_s`` measured, grouped per
   ``(backend, scope)`` — training steps and graft-fleet serving ticks
   calibrate side by side (the worker stamps ``scope: serve_decode``).
   Each run's FIRST window is dropped when more follow (it absorbs the
   compile); a single-window run keeps its only evidence.

2. **Fit** — :func:`fit_entry`: per-group linear coefficients
   ``seconds = base_s + s_per_flop·flops_proxy + s_per_byte·bytes_moved``
   by iteratively-reweighted (Huber) least squares — deterministic, pure
   numpy, no RNG — with non-negativity enforced by drop-and-refit, an
   all-zero feature recorded as *unidentifiable* (``None``, distinct
   from an identified ``0.0``), and loud :class:`CalibrationError`
   refusals for fewer-than-:data:`MIN_SAMPLES` or degenerate
   (constant-feature) inputs instead of extrapolating from one point.

3. **Commit** — ``analysis_results/cost_calibration.json`` (the
   ``search_pareto.json`` pattern: version pin, unknown-key rejection,
   merge semantics per entry; ``tools/graft_calibrate.py`` banks it).
   Every entry embeds its *training samples*, so the artifact is
   self-verifying: refitting the embedded samples must reproduce the
   committed coefficients byte-for-byte — a perturbed coefficient is
   caught hermetically, with no telemetry on disk.

4. **Gate** — rule **R016** extends the R014 ratchet: ERROR when the
   committed artifact is self-inconsistent (perturbed coefficients /
   residual evidence), when its jax signature no longer matches the
   interpreter, when fresh telemetry's residuals drift past tolerance
   under the committed coefficients, or when the committed search
   frontier's ``predicted_seconds`` re-rank is stale against the
   committed calibration (including a winner now *dominated* under
   calibrated seconds). Wired into full-matrix ``graft_lint --cost``
   next to R014; ``tools/graft_calibrate.py verify`` is the standalone
   entry (rc 1 on any ERROR).

``analysis/search.py`` cashes the artifact in: ``run_space(...,
calibration=...)`` appends a ``predicted_seconds`` objective priced
under the calibrated model and a ``seconds_rank`` over the frontier —
the total order in *seconds* the proxy objectives could not give.
"""

import json
import math
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.analysis.core import ERROR, INFO, LAYER_COST, Finding, rule

CALIBRATION_VERSION = 1
#: R016 residual-drift tolerance: fresh telemetry's median |relative
#: error| under the committed coefficients may exceed the committed fit's
#: own residual level by at most this many error-fraction points
DEFAULT_RESIDUAL_TOLERANCE = 0.10
#: loud-refusal floor: a linear model with an intercept has no business
#: extrapolating from fewer points than this
MIN_SAMPLES = 4
#: fixed IRLS iteration budget — determinism over adaptive stopping
IRLS_ITERS = 8
_HUBER_K = 1.345
#: (price metric, coefficient name) in fixed fit order
FEATURES = (("flops_proxy", "s_per_flop"), ("bytes_moved", "s_per_byte"))
#: self-consistency slack for the hermetic refit check (float round-trip)
_REFIT_RTOL = 1e-9
_MAX_FINDINGS_PER_SCENARIO = 8

_ARTIFACT_TOP_KEYS = {"version", "tolerance", "jax_version", "entries"}
_ENTRY_KEYS = {"coeffs", "fit", "samples"}

#: the *uncalibrated* conversion R016's whole reason to exist replaces —
#: documented nominal peaks per backend, (FLOP/s, bytes/s):
#: one modern x86 core ~1e11 fp32 FLOP/s FMA peak / ~1e10 B/s sustained
#: stream; a TPU v4 chip 2.75e14 bf16 FLOP/s / 1.2e12 B/s HBM. PERF.md
#: §PR18 measures the calibrated model against exactly this baseline.
NAIVE_PEAKS: Dict[str, Tuple[float, float]] = {
    "cpu": (1.0e11, 1.0e10),
    "tpu": (2.75e14, 1.2e12),
}


class CalibrationError(ValueError):
    """A fit refused: too few samples, or degenerate inputs a linear
    model must not extrapolate from. Loud by contract."""


# ---------------------------------------------------------------------------
# artifact IO (merge semantics, the search_pareto.json pattern)
# ---------------------------------------------------------------------------
def default_calibration_path() -> str:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "analysis_results", "cost_calibration.json")


def load_calibration(path: Optional[str] = None) -> Dict:
    path = path or default_calibration_path()
    if not os.path.exists(path):
        return {"version": CALIBRATION_VERSION,
                "tolerance": DEFAULT_RESIDUAL_TOLERANCE, "entries": {}}
    with open(path) as fh:
        artifact = json.load(fh)
    if artifact.get("version") != CALIBRATION_VERSION:
        raise ValueError(f"calibration artifact {path} has version "
                         f"{artifact.get('version')}, expected "
                         f"{CALIBRATION_VERSION} — regenerate with "
                         f"tools/graft_calibrate.py fit --update")
    unknown = set(artifact) - _ARTIFACT_TOP_KEYS
    if unknown:
        raise ValueError(f"calibration artifact {path} has unknown top-level "
                         f"keys {sorted(unknown)}")
    for key, entry in artifact.get("entries", {}).items():
        bad = set(entry) - _ENTRY_KEYS
        if bad:
            raise ValueError(f"calibration entry {key!r} has unknown keys "
                             f"{sorted(bad)} (valid: {sorted(_ENTRY_KEYS)})")
    artifact.setdefault("tolerance", DEFAULT_RESIDUAL_TOLERANCE)
    artifact.setdefault("entries", {})
    return artifact


def calibration_from(entries: Dict[str, dict],
                     prior: Optional[Dict] = None) -> Dict:
    """Bank fitted entries. MERGE semantics: refitting one (backend,
    scope) group never drops another's entry — dropping it would silently
    un-price every consumer of that scope."""
    import jax
    merged = dict((prior or {}).get("entries", {}))
    merged.update(entries)
    return {"version": CALIBRATION_VERSION,
            "tolerance": (prior or {}).get("tolerance",
                                           DEFAULT_RESIDUAL_TOLERANCE),
            "jax_version": jax.__version__,
            "entries": dict(sorted(merged.items()))}


def calibration_entry(calibration: Optional[Dict], backend: Optional[str] = None,
                      scope: str = "train_step") -> Tuple[Optional[dict], str]:
    """(entry or None, the ``<backend>/<scope>`` key looked up)."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    key = f"{backend}/{scope}"
    return (calibration or {}).get("entries", {}).get(key), key


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def calibrated_seconds(metrics: Dict, coeffs: Dict) -> Optional[float]:
    """Predicted wall seconds for one static price under fitted
    coefficients. ``None`` when the price exercises a feature the fit
    could not identify (coefficient ``None`` with a nonzero input) —
    unpriceable is an answer, a silently dropped term is not."""
    total = coeffs.get("base_s") or 0.0
    for feat, cname in FEATURES:
        x = metrics.get(feat) or 0
        if not x:
            continue
        c = coeffs.get(cname)
        if c is None:
            return None
        total += c * float(x)
    return total


def naive_seconds(metrics: Dict, backend: Optional[str] = None) -> Optional[float]:
    """The uncalibrated conversion (flops ÷ nominal peak FLOP/s + bytes ÷
    nominal peak B/s) — PERF.md §PR18's comparison baseline, never a
    consumer-facing prediction."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    peaks = NAIVE_PEAKS.get(backend)
    if peaks is None:
        return None
    return (float(metrics.get("flops_proxy") or 0) / peaks[0]
            + float(metrics.get("bytes_moved") or 0) / peaks[1])


def residual_summary(samples: List[dict], coeffs: Dict) -> Dict[str, Any]:
    """Per-coefficient-set residual evidence over a sample set: the
    |relative error| distribution of the model's predictions."""
    errs = []
    unpriced = 0
    for s in samples:
        pred = calibrated_seconds(s, coeffs)
        y = s.get("measured_s")
        if pred is None or not y:
            unpriced += 1
            continue
        errs.append(abs(pred - y) / y)
    errs.sort()

    def pct(p):
        return errs[min(len(errs) - 1, int(math.ceil(p / 100.0 * len(errs))) - 1)]

    if not errs:
        return {"samples": 0, "unpriced": unpriced}
    return {"samples": len(errs), "unpriced": unpriced,
            "median_abs_rel_err": pct(50), "p90_abs_rel_err": pct(90),
            "max_abs_rel_err": errs[-1]}


# ---------------------------------------------------------------------------
# the fitter
# ---------------------------------------------------------------------------
def _irls(X: np.ndarray, y: np.ndarray, iters: int = IRLS_ITERS) -> np.ndarray:
    """Huber IRLS: plain least squares re-solved ``iters`` times with
    weights shrinking residuals past 1.345·MAD — a fixed iteration budget
    (not a convergence test) so two fits of the same data are bit-equal."""
    w = np.ones(len(y))
    beta = np.zeros(X.shape[1])
    for _ in range(max(1, iters)):
        beta, _, _, _ = np.linalg.lstsq(X * w[:, None], y * w, rcond=None)
        r = y - X @ beta
        scale = 1.4826 * float(np.median(np.abs(r)))
        if scale <= 0.0:
            break  # exact fit: weights would divide by zero, and can't improve
        a = np.abs(r) / (_HUBER_K * scale)
        w = np.sqrt(np.where(a <= 1.0, 1.0, 1.0 / a))
    return beta


def fit_entry(samples: List[dict], min_samples: int = MIN_SAMPLES) -> dict:
    """Fit one (backend, scope) group. Returns the committed-artifact
    entry: coefficients, residual evidence, and the samples themselves
    (the hermetic self-verification set R016 refits)."""
    samples = list(samples)
    if len(samples) < min_samples:
        raise CalibrationError(
            f"{len(samples)} sample(s) < minimum {min_samples} — refusing to "
            f"fit a 3-coefficient model; accumulate more telemetry windows")
    y = np.asarray([float(s["measured_s"]) for s in samples])
    if np.any(y <= 0):
        raise CalibrationError("non-positive measured_s in the sample set")
    coeffs: Dict[str, Optional[float]] = {"base_s": None}
    cols, names, scales = [], [], []
    for feat, cname in FEATURES:
        x = np.asarray([float(s.get(feat) or 0) for s in samples])
        if not np.any(x):
            coeffs[cname] = None  # unidentifiable: the data never moved it
            continue
        if np.ptp(x) == 0.0:
            raise CalibrationError(
                f"degenerate input: {feat} is constant ({x[0]:g}) across all "
                f"{len(samples)} samples — a slope fitted here would be pure "
                f"extrapolation; vary the workload (or fit intercept-only "
                f"telemetry under a different scope)")
        scale = float(np.max(x))
        cols.append(x / scale)
        names.append(cname)
        scales.append(scale)
        coeffs[cname] = 0.0
    # non-negativity by drop-and-refit, first negative in fixed column
    # order each round (deterministic): a negative seconds-per-flop is a
    # confounded fit, not a discount
    active = [True] * (1 + len(cols))  # [intercept] + feature columns
    beta_full = np.zeros(1 + len(cols))
    while True:
        X = np.column_stack(
            [np.ones(len(y)) if i == 0 else cols[i - 1]
             for i, on in enumerate(active) if on])
        if X.shape[1] == 0:
            break
        beta = _irls(X, y)
        beta_full = np.zeros(1 + len(cols))
        beta_full[[i for i, on in enumerate(active) if on]] = beta
        neg = next((i for i, on in enumerate(active)
                    if on and beta_full[i] < 0.0), None)
        if neg is None:
            break
        active[neg] = False
        beta_full[neg] = 0.0
    coeffs["base_s"] = float(beta_full[0])
    for j, cname in enumerate(names):
        coeffs[cname] = float(beta_full[1 + j] / scales[j])
    entry_samples = [_canonical_sample(s) for s in samples]
    fit = {"samples": len(samples),
           "features": names,
           "clamped": [n for i, n in enumerate(["base_s"] + names)
                       if not active[i]],
           "irls_iters": IRLS_ITERS}
    fit.update({k: v for k, v in residual_summary(entry_samples, coeffs).items()
                if k not in ("samples",)})
    return {"coeffs": coeffs, "fit": fit, "samples": entry_samples}


def _canonical_sample(s: dict) -> dict:
    out = {"flops_proxy": int(s.get("flops_proxy") or 0),
           "bytes_moved": int(s.get("bytes_moved") or 0),
           "measured_s": float(s["measured_s"])}
    for k in ("window_steps", "source"):
        if s.get(k) is not None:
            out[k] = s[k]
    return out


def fit_groups(groups: Dict[str, List[dict]], min_samples: int = MIN_SAMPLES,
               log=None) -> Tuple[Dict[str, dict], Dict[str, str]]:
    """Fit every (backend, scope) group; refusals are collected per key
    (and reported), never silently dropped."""
    entries, refused = {}, {}
    for key in sorted(groups):
        try:
            entries[key] = fit_entry(groups[key], min_samples=min_samples)
            if log:
                c = entries[key]["coeffs"]
                log(f"fit {key}: base_s={c['base_s']:.6g} "
                    f"s_per_flop={c['s_per_flop']} s_per_byte={c['s_per_byte']} "
                    f"med|rel|={entries[key]['fit'].get('median_abs_rel_err')}")
        except CalibrationError as e:
            refused[key] = str(e)
            if log:
                log(f"refused {key}: {e}")
    return entries, refused


# ---------------------------------------------------------------------------
# sample collection (telemetry JSONL + trace_report --drift sidecars)
# ---------------------------------------------------------------------------
def collect_samples(paths: Iterable[str],
                    default_scope: str = "train_step") -> Dict[str, List[dict]]:
    """Walk run directories / ``telemetry.jsonl`` files / ``--drift``
    sidecar JSONs into per-``<backend>/<scope>`` sample groups. Runs
    without a usable static price (disabled, or stamped ``{"error":...}``)
    contribute nothing; deterministic order (input order, event order)
    so two collections over the same files are identical.

    graft-prefix-cache separation: a serving run whose header declares
    ``prefix_cache: "on"`` skips part of prefill (restored KV rows), so
    its tick timings fit a DIFFERENT cost line than full-prefill serving
    — those runs group under ``<scope>_cached``. Serve runs MISSING the
    ``prefix_cache``/``cached_prefix_tokens`` header fields (pre-PR-19
    telemetry) are ambiguous — they cannot be pooled with marked runs of
    the same group without silently mixing the two populations, so a mix
    raises :class:`CalibrationError` instead of fitting garbage.

    graft-rlhf separation (same pattern): ``rlhf_rollout`` /
    ``rlhf_learner`` scopes join the fit set. An overlapped rollout
    run's tick timings carry interleaved learner work (the overlap being
    priced!), so runs whose header declares ``rlhf_overlap: "on"`` group
    under ``<scope>_overlap``; rlhf runs missing the ``rlhf_overlap``
    header field are ambiguous and a marked/unmarked mix in one group
    refuses loudly."""
    groups: Dict[str, List[dict]] = {}
    serve_marking: Dict[str, set] = {}
    rlhf_marking: Dict[str, set] = {}
    for path in paths:
        for run, price, windows in _iter_runs(path):
            if not isinstance(price, dict) or price.get("error") \
                    or not price.get("flops_proxy"):
                continue
            backend = (run or {}).get("backend") or "unknown"
            scope = (run or {}).get("scope") or default_scope
            if scope.startswith("serve"):
                marked = ("prefix_cache" in (run or {})
                          or "cached_prefix_tokens" in (run or {}))
                serve_marking.setdefault(f"{backend}/{scope}",
                                         set()).add(marked)
                if marked and (run or {}).get("prefix_cache") == "on":
                    scope = f"{scope}_cached"
            elif scope.startswith("rlhf"):
                marked = "rlhf_overlap" in (run or {})
                rlhf_marking.setdefault(f"{backend}/{scope}",
                                        set()).add(marked)
                if marked and (run or {}).get("rlhf_overlap") == "on":
                    scope = f"{scope}_overlap"
            key = f"{backend}/{scope}"
            usable = windows[1:] if len(windows) > 1 else windows
            source = (run or {}).get("config_sig") or (run or {}).get("bench") \
                or os.path.basename(os.path.dirname(os.path.abspath(path))) or "run"
            for w in usable:
                med = w.get("median_step_s")
                if not med or med <= 0:
                    continue
                groups.setdefault(key, []).append({
                    "flops_proxy": int(price.get("flops_proxy") or 0),
                    "bytes_moved": int(price.get("bytes_moved") or 0),
                    "measured_s": float(med),
                    "window_steps": int(w.get("window_steps") or 0),
                    "source": str(source)})
    mixed = sorted(k for k, flags in serve_marking.items() if len(flags) > 1)
    if mixed:
        raise CalibrationError(
            f"serve sample group(s) {mixed} mix runs WITH the "
            f"prefix_cache/cached_prefix_tokens header fields and runs "
            f"WITHOUT them — unmarked runs may contain cached-prefill "
            f"ticks, so pooling them with full-prefill samples would fit "
            f"a meaningless cost line; re-collect the unmarked runs with "
            f"current telemetry (fleet/worker.py stamps the fields) or "
            f"drop them from the collection")
    mixed_rlhf = sorted(k for k, flags in rlhf_marking.items()
                        if len(flags) > 1)
    if mixed_rlhf:
        raise CalibrationError(
            f"rlhf sample group(s) {mixed_rlhf} mix runs WITH the "
            f"rlhf_overlap header field and runs WITHOUT it — unmarked "
            f"runs may contain overlapped-learner ticks, so pooling them "
            f"with rollout-only samples would fit a meaningless cost "
            f"line; re-collect the unmarked runs with current telemetry "
            f"(the run header carries the field) or drop them from "
            f"the collection")
    return {k: groups[k] for k in sorted(groups)}


def _iter_runs(path: str):
    """Yield (run_info, static_price, drift_windows) per run in a
    telemetry JSONL (a file may hold several runs back to back), a run
    dir containing one, or a ``trace_report --drift`` sidecar JSON."""
    from deepspeed_tpu.runtime.telemetry.sink import iter_events

    if os.path.isdir(path):
        from deepspeed_tpu.runtime.telemetry.core import TELEMETRY_FILE
        candidate = os.path.join(path, TELEMETRY_FILE)
        if not os.path.exists(candidate):
            raise FileNotFoundError(f"no {TELEMETRY_FILE} under {path}")
        path = candidate
    if path.endswith(".json"):
        with open(path) as fh:
            doc = json.load(fh)
        if "windows" not in doc:
            raise ValueError(f"{path}: not a trace_report --drift sidecar "
                             f"(no 'windows' key)")
        yield doc.get("run") or {}, doc.get("predicted"), list(doc["windows"])
        return
    run, price, windows = None, None, []
    for rec in iter_events(path):
        kind = rec.get("event")
        if kind == "run_start":
            if windows:
                yield run, price, windows
            run, price, windows = rec.get("run") or {}, rec.get("static_price"), []
        elif kind == "drift":
            windows.append(rec)
    if windows:
        yield run, price, windows


# ---------------------------------------------------------------------------
# R016 — the calibration ratchet
# ---------------------------------------------------------------------------
@rule("R016", "the committed cost calibration must not drift stale", ERROR,
      LAYER_COST)
def r016_calibration_drift(calibration: Dict,
                           search_artifact: Optional[Dict] = None,
                           current_samples: Optional[Dict[str, List[dict]]] = None,
                           tolerance: Optional[float] = None) -> List[Finding]:
    """Judge the committed ``cost_calibration.json``: ERROR when (a) an
    entry is self-inconsistent — refitting its embedded samples does not
    reproduce the committed coefficients/residual evidence (a perturbed
    or hand-edited artifact; hermetic, no telemetry needed); (b) the
    artifact's jax signature no longer matches the interpreter (the
    coefficients were fitted against a different dispatch stack); (c)
    fresh telemetry's residuals under the committed coefficients exceed
    the committed fit's own error level by more than ``tolerance``; or
    (d) the committed search frontier's ``predicted_seconds`` re-rank is
    stale against the calibration — recomputed seconds disagree, the
    seconds_rank is unsorted, or a committed winner is now *dominated*
    once calibrated seconds joins the objectives. An absent artifact or
    a not-yet-re-ranked space reports INFO (bank explicitly with
    ``tools/graft_calibrate.py fit --update`` /
    ``tools/graft_search.py --update``, never silently)."""
    findings: List[Finding] = []
    entries = calibration.get("entries", {})
    if not entries:
        findings.append(Finding(
            rule="R016", severity=INFO, scenario="calibration:artifact",
            message="no committed calibration — fit and bank with "
                    "tools/graft_calibrate.py fit <runs...> --update"))
        return findings
    tol = float(tolerance if tolerance is not None
                else calibration.get("tolerance", DEFAULT_RESIDUAL_TOLERANCE))
    import jax
    if calibration.get("jax_version") \
            and calibration["jax_version"] != jax.__version__:
        findings.append(Finding(
            rule="R016", severity=ERROR, scenario="calibration:artifact",
            message=f"jax signature mismatch: artifact fitted under "
                    f"{calibration['jax_version']}, interpreter runs "
                    f"{jax.__version__} — refit with tools/graft_calibrate.py",
            location="jax_version"))
    for key, entry in sorted(entries.items()):
        scenario = f"calibration:{key}"
        per: List[Finding] = []
        per.extend(_entry_self_consistency(scenario, entry))
        if current_samples and current_samples.get(key):
            cur = residual_summary(
                [_canonical_sample(s) for s in current_samples[key]],
                entry["coeffs"])
            base_err = entry.get("fit", {}).get("median_abs_rel_err")
            cur_err = cur.get("median_abs_rel_err")
            if cur_err is None:
                per.append(Finding(
                    rule="R016", severity=ERROR, scenario=scenario,
                    message="current telemetry is unpriceable under the "
                            "committed coefficients (unidentified feature now "
                            "nonzero) — refit",
                    location="residuals"))
            elif base_err is not None and cur_err > base_err + tol:
                per.append(Finding(
                    rule="R016", severity=ERROR, scenario=scenario,
                    message=f"calibration residuals drifted: median |rel err| "
                            f"{cur_err:.3f} on current telemetry vs "
                            f"{base_err:.3f} committed (+{tol:.0%} tolerance) "
                            f"— the machine changed; refit and re-bank",
                    location="residuals"))
        findings.extend(per[:_MAX_FINDINGS_PER_SCENARIO])
    if search_artifact is not None:
        findings.extend(_frontier_rerank_findings(calibration, search_artifact))
    return findings


def _rel_close(a: Optional[float], b: Optional[float],
               rtol: float = _REFIT_RTOL) -> bool:
    # purely relative: coefficients live at 1e-12 scale, so any absolute
    # floor would wave perturbations through
    if a is None or b is None:
        return a is None and b is None
    if a == b:
        return True
    return abs(a - b) <= rtol * max(abs(a), abs(b))


def _entry_self_consistency(scenario: str, entry: dict) -> List[Finding]:
    out: List[Finding] = []
    try:
        refit = fit_entry(entry.get("samples") or [])
    except CalibrationError as e:
        return [Finding(rule="R016", severity=ERROR, scenario=scenario,
                        message=f"embedded sample set no longer fits: {e}",
                        location="samples")]
    committed = entry.get("coeffs", {})
    for cname in ("base_s",) + tuple(c for _, c in FEATURES):
        if not _rel_close(committed.get(cname), refit["coeffs"].get(cname)):
            out.append(Finding(
                rule="R016", severity=ERROR, scenario=scenario,
                message=f"coefficient {cname} = {committed.get(cname)} does "
                        f"not refit from the embedded samples "
                        f"(got {refit['coeffs'].get(cname)}) — perturbed or "
                        f"hand-edited artifact; re-bank with "
                        f"tools/graft_calibrate.py fit --update",
                location=cname))
    for metric in ("median_abs_rel_err", "p90_abs_rel_err", "max_abs_rel_err"):
        if not _rel_close(entry.get("fit", {}).get(metric),
                          refit["fit"].get(metric), rtol=1e-6):
            out.append(Finding(
                rule="R016", severity=ERROR, scenario=scenario,
                message=f"residual evidence {metric} = "
                        f"{entry.get('fit', {}).get(metric)} inconsistent with "
                        f"the embedded samples (recomputed "
                        f"{refit['fit'].get(metric)})",
                location=f"fit.{metric}"))
    return out


def _frontier_rerank_findings(calibration: Dict,
                              search_artifact: Dict) -> List[Finding]:
    from deepspeed_tpu.analysis.search import pareto  # lazy: import cycle
    findings: List[Finding] = []
    entries = calibration.get("entries", {})
    for name, space in sorted(search_artifact.get("spaces", {}).items()):
        scenario = f"calibration:search:{name}"
        per: List[Finding] = []
        objectives = list(space.get("objectives") or ())
        if "predicted_seconds" not in objectives:
            findings.append(Finding(
                rule="R016", severity=INFO, scenario=scenario,
                message="space not re-ranked under the committed calibration "
                        "— regenerate with tools/graft_search.py --update"))
            continue
        prov = space.get("calibration") or {}
        entry = entries.get(prov.get("key") or "")
        if entry is None:
            findings.append(Finding(
                rule="R016", severity=ERROR, scenario=scenario,
                message=f"space re-ranked under calibration key "
                        f"{prov.get('key')!r} that the committed artifact no "
                        f"longer carries — regenerate the frontier",
                location="calibration.key"))
            continue
        cands = space.get("candidates", {})
        recomputed: Dict[str, Optional[float]] = {}
        for cid, cand in cands.items():
            metrics = cand.get("metrics", {})
            sec = calibrated_seconds(metrics, entry["coeffs"])
            recomputed[cid] = sec
            stored = metrics.get("predicted_seconds")
            if sec is None or stored is None or not _rel_close(stored, sec):
                per.append(Finding(
                    rule="R016", severity=ERROR, scenario=scenario,
                    message=f"stale re-rank: {cid} predicted_seconds {stored} "
                            f"vs {sec} under the committed coefficients — "
                            f"regenerate with tools/graft_search.py --update",
                    location=cid))
        if not per:
            shadow = {cid: {"metrics": dict(c.get("metrics", {}),
                                            predicted_seconds=recomputed[cid])}
                      for cid, c in cands.items()}
            frontier_now, dominated_by = pareto(shadow, objectives)
            for cid in space.get("frontier", []):
                if cid not in frontier_now:
                    per.append(Finding(
                        rule="R016", severity=ERROR, scenario=scenario,
                        message=f"committed winner {cid} is dominated under "
                                f"calibrated seconds (by "
                                f"{dominated_by.get(cid, [])[:3]}) — the "
                                f"frontier a chip window would measure is "
                                f"stale",
                        location=cid))
            rank = space.get("seconds_rank")
            if rank is not None:
                secs = [recomputed.get(cid) for cid in rank]
                if (sorted(rank) != sorted(space.get("frontier", []))
                        or any(s is None for s in secs)
                        or any(secs[i] > secs[i + 1]
                               for i in range(len(secs) - 1))):
                    per.append(Finding(
                        rule="R016", severity=ERROR, scenario=scenario,
                        message="seconds_rank provenance is not the frontier "
                                "sorted by calibrated seconds — regenerate "
                                "with tools/graft_search.py --update",
                        location="seconds_rank"))
        findings.extend(per[:_MAX_FINDINGS_PER_SCENARIO])
    return findings


def verify_calibration(calibration_path: Optional[str] = None,
                       search_pareto_path: Optional[str] = None,
                       runs: Optional[List[str]] = None,
                       tolerance: Optional[float] = None,
                       log=None) -> List[Finding]:
    """Load the committed artifacts and judge them with R016 — the shared
    entry point for ``graft_lint --cost`` and
    ``tools/graft_calibrate.py verify``. ``runs`` (telemetry run dirs /
    JSONLs / drift sidecars) additionally enables the fresh-telemetry
    residual-drift check."""
    calibration = load_calibration(calibration_path)
    search_artifact = None
    if search_pareto_path is None:
        search_pareto_path = os.path.join(
            os.path.dirname(default_calibration_path()), "search_pareto.json")
    if os.path.exists(search_pareto_path):
        from deepspeed_tpu.analysis.search import load_search_artifact
        search_artifact = load_search_artifact(search_pareto_path)
    current = collect_samples(runs) if runs else None
    if log and current:
        for key, samples in current.items():
            log(f"collected {len(samples)} current sample(s) for {key}")
    return r016_calibration_drift(calibration, search_artifact, current,
                                  tolerance=tolerance)
