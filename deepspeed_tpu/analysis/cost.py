"""graft-audit cost engine: static memory + collective cost per program,
rules R009-R013, and the ratcheted cost baseline.

PR 7's rules answered yes/no questions; this layer answers *how much* —
statically estimated peak live bytes (:mod:`.memory`), the collective
inventory with analytic wire bytes (:mod:`.hlo_cost`), and a
cross-check against the backend's own ``cost_analysis()``/
``memory_analysis()`` where the compiled executable provides them. On
top sit the quantitative gates:

* **R009** — per-scenario collective-signature drift. Scenario metadata
  declares ``collective_signature``: a list of assertions over the
  inventory, each ``{"layer", "kind", "count"|"min_count"|"max_count",
  "max_bytes", "backends", "note"}``. Entries whose layer has no
  inventory on this run (or whose ``backends`` excludes this backend —
  e.g. the reduce-scatter expectation XLA:CPU decomposes away) are
  recorded as *unchecked*, never silently passed.
* **R010** — statically estimated ``peak_transient_bytes`` above the
  metadata-declared ``activation_budget_bytes``. The pre-wired gate for
  the ROADMAP-2 1F1B refactor: the pipe engine stamps its budget from
  config (``pipeline.activation_budget_mb``) or ``DS_PIPE_ACT_BUDGET_MB``.
* **R011** — redundant collectives: identical (primitive, operands,
  axes) collective eqns, or a collective inside ``scan`` whose operands
  are loop-invariant (hoistable: it pays per-tick wire bytes for a
  constant).
* **R012** — host-transfer bytes in the step program above
  ``host_transfer_budget_bytes`` (default 1 MiB). R003 flags the
  *presence* of host primitives; R012 prices the ones metadata allowed.
* **R013** — the cost ratchet: current peak bytes / wire bytes /
  collective counts vs the committed
  ``analysis_results/cost_baseline.json``, gating on growth beyond
  tolerance (same contract as PR 7's fingerprint baseline; shrinkage
  reports as an improvement to bank with ``--update-baseline``).
"""

import dataclasses
import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

from deepspeed_tpu.analysis import hlo_cost
from deepspeed_tpu.analysis.core import (ERROR, INFO, LAYER_COST, WARN, Finding,
                                         cost_rules, rule)
from deepspeed_tpu.analysis.memory import MemoryEstimate, estimate_memory
from deepspeed_tpu.analysis.program import ProgramAnalyzer, ProgramInfo, aval_bytes

COST_BASELINE_VERSION = 1
DEFAULT_TOLERANCE = 0.05  # relative growth allowed before R013 gates
_ABS_FLOOR = 64 << 10  # ignore sub-64KiB absolute drift (fingerprint noise)

#: signature-entry schema (unknown keys rejected loudly, like waivers)
_SIG_KEYS = {"layer", "kind", "count", "min_count", "max_count", "max_bytes",
             "backends", "note"}


@dataclasses.dataclass
class CostInfo:
    """Everything the cost rules judge for one program."""

    program: str
    memory: MemoryEstimate
    ops: List[hlo_cost.CollectiveOp]
    inventory: Dict[str, Dict[str, Any]]  # layer -> {counts, bytes_moved, bytes_by_axis}
    backend_stats: Optional[Dict[str, Any]] = None  # compiled cross-check
    compile_error: str = ""
    unchecked_signature: Optional[List[dict]] = None

    def counts(self, layer: str) -> Dict[str, int]:
        return dict(self.inventory.get(layer, {}).get("counts", {}))

    def bytes_moved(self) -> Dict[str, int]:
        return {layer: inv["bytes_moved"] for layer, inv in self.inventory.items()}

    def to_dict(self) -> dict:
        return {
            "memory": self.memory.to_dict(),
            "collectives": {layer: {k: v for k, v in inv.items()}
                            for layer, inv in self.inventory.items()},
            "backend_stats": self.backend_stats,
            "compile_error": self.compile_error,
            "unchecked_signature": self.unchecked_signature or [],
        }


def _backend_stats(compiled) -> Dict[str, Any]:
    """Flops + per-device memory stats from the compiled executable —
    the on-backend numbers the static estimate is cross-checked against.
    ``cost_analysis()`` may come back as a list of per-computation dicts
    (the PR 5 autotuner handling)."""
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        entry = ca[0] if isinstance(ca, (list, tuple)) and ca else ca
        if isinstance(entry, dict):
            for key in ("flops", "bytes accessed", "transcendentals"):
                if key in entry:
                    out[key.replace(" ", "_")] = float(entry[key])
    except Exception as e:  # noqa: BLE001 — stats are evidence, never fatal
        out["cost_analysis_error"] = f"{type(e).__name__}: {str(e)[:120]}"
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for key in ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "alias_size_in_bytes",
                        "host_argument_size_in_bytes"):
                val = getattr(ma, key, None)
                if val is not None:
                    out[key] = int(val)
    except Exception as e:  # noqa: BLE001
        out["memory_analysis_error"] = f"{type(e).__name__}: {str(e)[:120]}"
    return out


def build_cost(program: ProgramInfo, analyzer: Optional[ProgramAnalyzer] = None,
               compile: bool = True) -> CostInfo:  # noqa: A002 — mirrors the CLI flag
    """Assemble the cost view of one program. ``compile=False`` keeps it
    trace-only (no second compile); the compiled inventory/stat layers then stay absent
    and signature entries against them report as unchecked."""
    analyzer = analyzer or ProgramAnalyzer(program)
    mesh_axes = program.metadata.get("mesh_axes")
    ops: List[hlo_cost.CollectiveOp] = []
    if program.jaxpr is not None:
        ops.extend(hlo_cost.jaxpr_collectives(analyzer, mesh_axes))
    if program.hlo_text:
        ops.extend(hlo_cost.stablehlo_collectives(program.hlo_text))
    backend_stats, compile_error = None, ""
    if compile:
        try:
            compiled = program.compiled()
            if compiled is not None:
                ops.extend(hlo_cost.compiled_collectives(compiled.as_text(), mesh_axes))
                backend_stats = _backend_stats(compiled)
        except Exception as e:  # noqa: BLE001 — a backend that cannot compile
            # the program is a report entry, not a crash
            compile_error = f"{type(e).__name__}: {str(e)[:200]}"
    inv = hlo_cost.inventory(ops)
    # logical kinds the cost engine counts on top of hlo_cost's ops
    sec_sites = _dense_dispatch_sites(program, analyzer)
    if sec_sites:
        inv.setdefault("jaxpr", {"counts": {}, "bytes_moved": 0, "bytes_by_axis": {}})
        inv["jaxpr"]["counts"]["dense_dispatch"] = sec_sites
    mem = estimate_memory(program)
    return CostInfo(program=program.name, memory=mem, ops=ops, inventory=inv,
                    backend_stats=backend_stats, compile_error=compile_error)


def _dense_dispatch_sites(program: ProgramInfo, analyzer: ProgramAnalyzer) -> int:
    """Distinct sites materializing a ``[*,S,E,C]``-signature intermediate
    (R001's shape test, counted rather than judged): the route-drift
    component of the MoE collective signature — a dense dispatch feeds the
    all-to-all endpoints with an O(S*E*C) einsum instead of a gather."""
    sigs = [tuple(s) for s in program.metadata.get("moe_sec", ())]
    if not sigs:
        return 0
    seen = set()
    for rec, aval in analyzer.iter_avals():
        if tuple(aval.shape)[-3:] in sigs:
            seen.add((tuple(aval.shape), rec.scope))
    return len(seen)


# ---------------------------------------------------------------------------
# R009 — collective-signature drift
# ---------------------------------------------------------------------------
def _validate_signature(entries: Iterable[dict]):
    for e in entries:
        unknown = set(e) - _SIG_KEYS
        if unknown:
            raise ValueError(f"collective_signature entry {e!r} has unknown keys "
                             f"{sorted(unknown)} (valid: {sorted(_SIG_KEYS)})")
        if "layer" not in e or "kind" not in e:
            raise ValueError(f"collective_signature entry {e!r} needs 'layer' and 'kind'")


@rule("R009", "per-scenario collective signature must not drift", ERROR, LAYER_COST)
def r009_collective_signature(program: ProgramInfo, cost: CostInfo) -> List[Finding]:
    """The comms schedule is part of a scenario's contract: sorted MoE =
    exactly two capacity-bounded all-to-all reshards per layer direction
    (and ZERO dense-dispatch einsums feeding them), ZeRO>=2 = param
    movement via all-gather with gradients reduce-scattered (declared
    per-backend: XLA:CPU decomposes RS, so that entry checks on TPU and
    is inventoried as unchecked here). Any count/byte drift from the
    declared signature is an ERROR — the drift that silently turns a
    banked TFLOPS number into fiction."""
    entries = list(program.metadata.get("collective_signature", ()))
    if not entries:
        return []
    _validate_signature(entries)
    import jax
    backend = jax.default_backend()
    findings = []
    cost.unchecked_signature = cost.unchecked_signature or []
    for e in entries:
        layer, kind = e["layer"], e["kind"]
        if e.get("backends") and backend not in e["backends"]:
            cost.unchecked_signature.append(dict(e, reason=f"backend {backend} excluded"))
            continue
        if layer not in cost.inventory:
            if layer == "compiled" and cost.compile_error:
                cost.unchecked_signature.append(dict(e, reason=cost.compile_error))
                continue
            # layer genuinely absent (e.g. trace-only run): unchecked
            cost.unchecked_signature.append(dict(e, reason=f"no {layer} inventory"))
            continue
        count = cost.counts(layer).get(kind, 0)
        want = e.get("count")
        if want is not None and count != want:
            findings.append(Finding(
                rule="R009", severity=ERROR, scenario=program.name,
                message=f"collective signature drift: expected exactly {want} "
                        f"{kind}@{layer}, found {count}"
                        + (f" ({e['note']})" if e.get("note") else ""),
                location=layer))
        lo, hi = e.get("min_count"), e.get("max_count")
        if lo is not None and count < lo:
            findings.append(Finding(
                rule="R009", severity=ERROR, scenario=program.name,
                message=f"collective signature drift: expected >={lo} "
                        f"{kind}@{layer}, found {count}"
                        + (f" ({e['note']})" if e.get("note") else ""),
                location=layer))
        if hi is not None and count > hi:
            findings.append(Finding(
                rule="R009", severity=ERROR, scenario=program.name,
                message=f"collective signature drift: expected <={hi} "
                        f"{kind}@{layer}, found {count}"
                        + (f" ({e['note']})" if e.get("note") else ""),
                location=layer))
        max_bytes = e.get("max_bytes")
        if max_bytes is not None:
            fat = [op for op in cost.ops
                   if op.layer == layer and op.kind == kind and op.bytes_in > max_bytes]
            for op in fat[:4]:
                findings.append(Finding(
                    rule="R009", severity=ERROR, scenario=program.name,
                    message=f"{kind}@{layer} moves {op.bytes_in} bytes "
                            f"(> declared max {max_bytes})"
                            + (f" ({e['note']})" if e.get("note") else ""),
                    location=f"{layer}:{op.scope or op.axes}"))
    return findings


# ---------------------------------------------------------------------------
# R010 — activation budget
# ---------------------------------------------------------------------------
@rule("R010", "static peak activations must fit the declared budget", ERROR, LAYER_COST)
def r010_activation_budget(program: ProgramInfo, cost: CostInfo) -> List[Finding]:
    """A schedule's activation bound is only real if something fails when
    it is exceeded. Programs that declare ``activation_budget_bytes``
    (the pipe engine stamps it from ``pipeline.activation_budget_mb``)
    gate their statically estimated transient peak against it — the
    CPU-checkable stand-in for the ROADMAP-2 ``<=1F1B`` bound, pre-wired
    so the refactor lands against a live gate."""
    budget = program.metadata.get("activation_budget_bytes")
    if not budget:
        return []
    peak = cost.memory.peak_transient_bytes
    if peak <= budget:
        return []
    # attribution reads the TRANSIENT timeline's own peak slot — the
    # total-peak slot may be params-dominated and name the wrong buffer
    top = cost.memory.top_transient[0] if cost.memory.top_transient else {}
    return [Finding(
        rule="R010", severity=ERROR, scenario=program.name,
        message=f"statically estimated peak activations {peak / 2**20:.1f} MiB "
                f"exceed declared budget {budget / 2**20:.1f} MiB "
                f"(largest live: {top.get('shape')} {top.get('dtype')} "
                f"@ {top.get('scope')})",
        location="memory")]


# ---------------------------------------------------------------------------
# R011 — redundant collectives
# ---------------------------------------------------------------------------
_COLLECTIVE_PRIMS = set(hlo_cost._PRIM_KIND)


@rule("R011", "no redundant or loop-invariant collectives", WARN, LAYER_COST)
def r011_redundant_collectives(program: ProgramInfo, cost: CostInfo,
                               analyzer: Optional[ProgramAnalyzer] = None) -> List[Finding]:
    """Two shapes of wasted wire bytes: (a) byte-identical collectives —
    same primitive, same operand vars, same axes — dispatched twice
    (XLA's CSE may or may not save you across fusion boundaries; the
    program shouldn't bet on it); (b) a collective inside a ``scan`` body
    whose operands derive only from loop *constants* — it moves the same
    bytes every tick and belongs hoisted above the loop."""
    if program.jaxpr is None:
        return []
    analyzer = analyzer or ProgramAnalyzer(program)
    findings: List[Finding] = []
    seen: Dict[tuple, int] = {}
    seen_eqns = set()
    for rec in analyzer.records():
        if rec.primitive not in _COLLECTIVE_PRIMS:
            continue
        # a shared sub-jaxpr (pjit/remat caches the body) reaches the walk
        # once per CALL SITE with the same eqn object — that is reuse on
        # different runtime data, not a duplicate dispatch
        if id(rec.eqn) in seen_eqns:
            continue
        seen_eqns.add(id(rec.eqn))
        key = (rec.primitive,
               tuple(id(v) for v in rec.eqn.invars if hasattr(v, "count")),
               str(rec.eqn.params.get("axes") or rec.eqn.params.get("axis_name")),
               str(rec.eqn.params.get("perm", "")))
        seen[key] = seen.get(key, 0) + 1
        if seen[key] == 2:  # report once per duplicate set
            findings.append(Finding(
                rule="R011", severity=WARN, scenario=program.name,
                message=f"duplicate {rec.primitive} over identical operands and "
                        f"axes — one dispatch of the result would do",
                location=rec.scope))
    # loop-invariant collectives inside scan bodies
    seen_scans = set()
    for rec in analyzer.records():
        if rec.primitive != "scan" or id(rec.eqn) in seen_scans:
            continue
        seen_scans.add(id(rec.eqn))
        closed = rec.eqn.params.get("jaxpr")
        body = getattr(closed, "jaxpr", closed)
        if body is None:
            continue
        num_consts = int(rec.eqn.params.get("num_consts", 0))
        variant = set()  # vars derived from carry/xs
        for v in body.invars[num_consts:]:
            variant.add(v)
        for eqn in body.eqns:
            derived = any(v in variant for v in eqn.invars if hasattr(v, "count"))
            if derived:
                variant.update(o for o in eqn.outvars)
            if (eqn.primitive.name in _COLLECTIVE_PRIMS and not derived
                    and any(hasattr(v, "count") for v in eqn.invars)):
                findings.append(Finding(
                    rule="R011", severity=WARN, scenario=program.name,
                    message=f"{eqn.primitive.name} inside scan on loop-invariant "
                            f"operands — pays per-tick wire bytes for a constant; "
                            f"hoist above the loop",
                    location=rec.scope + "/scan"))
    return findings


# ---------------------------------------------------------------------------
# R012 — host-transfer bytes
# ---------------------------------------------------------------------------
_HOST_PRIMS = ("device_put", "io_callback", "pure_callback", "outside_call",
               "infeed", "outfeed", "debug_callback")


@rule("R012", "host-transfer bytes in the step must fit the budget", WARN, LAYER_COST)
def r012_host_transfer_bytes(program: ProgramInfo, cost: CostInfo,
                             analyzer: Optional[ProgramAnalyzer] = None) -> List[Finding]:
    """R003 bans host primitives outright (with an allowlist for paths
    that intentionally stream, e.g. offload); this rule prices whatever
    survived: total bytes crossing the host boundary per step above
    ``host_transfer_budget_bytes`` (default 1 MiB) is a WARN — the PCIe
    tax the offload A/B rungs measure on chip, now visible statically."""
    if program.jaxpr is None:
        return []
    budget = int(program.metadata.get("host_transfer_budget_bytes", 1 << 20))
    analyzer = analyzer or ProgramAnalyzer(program)
    total, sites = 0, 0
    for rec in analyzer.records():
        if rec.primitive in _HOST_PRIMS:
            sites += 1
            total += max(
                sum(aval_bytes(getattr(v, "aval", None)) for v in rec.eqn.invars
                    if hasattr(v, "aval")),
                sum(aval_bytes(v.aval) for v in rec.eqn.outvars if hasattr(v, "aval")))
    if total <= budget:
        return []
    return [Finding(
        rule="R012", severity=WARN, scenario=program.name,
        message=f"{total} bytes cross the host boundary per step over {sites} "
                f"site(s) (budget {budget}): every dispatch pays this transfer",
        location="host")]


# ---------------------------------------------------------------------------
# R013 — the cost ratchet
# ---------------------------------------------------------------------------
_BASELINE_PROGRAM_KEYS = {"peak_bytes", "peak_transient_bytes", "bytes_moved",
                          "collective_counts"}
_BASELINE_TOP_KEYS = {"version", "tolerance", "programs", "jax_version"}


def load_cost_baseline(path: str) -> Dict:
    """Committed cost baseline, unknown keys rejected loudly (a typo'd
    key would silently stop ratcheting the metric it meant to pin)."""
    if not os.path.exists(path):
        return {"version": COST_BASELINE_VERSION, "tolerance": DEFAULT_TOLERANCE,
                "programs": {}}
    with open(path) as fh:
        baseline = json.load(fh)
    if baseline.get("version") != COST_BASELINE_VERSION:
        raise ValueError(f"cost baseline {path} has version {baseline.get('version')}, "
                         f"expected {COST_BASELINE_VERSION} — regenerate with "
                         f"--cost --update-baseline")
    unknown = set(baseline) - _BASELINE_TOP_KEYS
    if unknown:
        raise ValueError(f"cost baseline {path} has unknown top-level keys "
                         f"{sorted(unknown)}")
    for name, entry in baseline.get("programs", {}).items():
        bad = set(entry) - _BASELINE_PROGRAM_KEYS
        if bad:
            raise ValueError(f"cost baseline entry {name!r} has unknown keys "
                             f"{sorted(bad)} (valid: {sorted(_BASELINE_PROGRAM_KEYS)})")
    baseline.setdefault("tolerance", DEFAULT_TOLERANCE)
    baseline.setdefault("programs", {})
    return baseline


def cost_baseline_from(cost_by_program: Dict[str, CostInfo],
                       prior: Optional[Dict] = None,
                       tolerance: float = DEFAULT_TOLERANCE) -> Dict:
    """A baseline acknowledging the current costs. MERGE semantics: a
    subset run (``--scenarios a,b --update-baseline``) refreshes only its
    own programs' entries — unlike the fingerprint baseline, dropping an
    entry here would *loosen* the ratchet for every untouched scenario."""
    import jax
    programs = dict((prior or {}).get("programs", {}))
    for name, cost in cost_by_program.items():
        programs[name] = {
            "peak_bytes": cost.memory.peak_bytes,
            "peak_transient_bytes": cost.memory.peak_transient_bytes,
            "bytes_moved": cost.bytes_moved(),
            "collective_counts": {layer: cost.counts(layer)
                                  for layer in cost.inventory},
        }
    return {"version": COST_BASELINE_VERSION,
            "tolerance": (prior or {}).get("tolerance", tolerance),
            "jax_version": jax.__version__,
            "programs": dict(sorted(programs.items()))}


@rule("R013", "static cost must not regress vs the committed baseline", ERROR, LAYER_COST)
def r013_cost_ratchet(cost_by_program: Dict[str, CostInfo],
                      baseline: Dict) -> List[Finding]:
    """The quantitative ratchet: per scenario, statically estimated peak
    bytes (total + transient), analytic wire bytes per inventory layer,
    and per-kind collective counts may not grow past the committed
    baseline (relative ``tolerance``, 64 KiB absolute floor for the byte
    metrics). Shrinkage and new scenarios report as INFO so improvements
    get banked explicitly with ``--cost --update-baseline``, never
    silently."""
    tol = float(baseline.get("tolerance", DEFAULT_TOLERANCE))
    findings: List[Finding] = []
    for name, cost in sorted(cost_by_program.items()):
        entry = baseline.get("programs", {}).get(name)
        if entry is None:
            findings.append(Finding(
                rule="R013", severity=INFO, scenario=name,
                message="no cost baseline entry — bank with --cost --update-baseline"))
            continue
        current = {"peak_bytes": cost.memory.peak_bytes,
                   "peak_transient_bytes": cost.memory.peak_transient_bytes}
        for metric, cur in current.items():
            base = entry.get(metric)
            if base is None:
                continue
            if cur > base * (1 + tol) and cur - base > _ABS_FLOOR:
                findings.append(Finding(
                    rule="R013", severity=ERROR, scenario=name,
                    message=f"cost regression: {metric} {cur / 2**20:.2f} MiB vs "
                            f"baseline {base / 2**20:.2f} MiB (tolerance {tol:.0%})",
                    location=metric))
            elif base > cur * (1 + tol) and base - cur > _ABS_FLOOR:
                findings.append(Finding(
                    rule="R013", severity=INFO, scenario=name,
                    message=f"cost improvement: {metric} {cur / 2**20:.2f} MiB vs "
                            f"baseline {base / 2**20:.2f} MiB — bank with "
                            f"--update-baseline",
                    location=metric))
        moved = cost.bytes_moved()
        for layer, base_moved in (entry.get("bytes_moved") or {}).items():
            cur_moved = moved.get(layer)
            if cur_moved is None:
                continue  # layer absent this run (e.g. compile skipped)
            if cur_moved > base_moved * (1 + tol) and cur_moved - base_moved > _ABS_FLOOR:
                findings.append(Finding(
                    rule="R013", severity=ERROR, scenario=name,
                    message=f"comms regression: {layer}-layer wire bytes "
                            f"{cur_moved} vs baseline {base_moved} (tolerance {tol:.0%})",
                    location=f"bytes_moved:{layer}"))
        for layer, base_counts in (entry.get("collective_counts") or {}).items():
            cur_counts = cost.counts(layer) if layer in cost.inventory else None
            if cur_counts is None:
                # layer absent this run (e.g. --no-compile): can't compare
                continue
            # union of kinds: a KIND the baseline never saw is exactly the
            # "new collectives appeared" class this rule exists to catch
            for kind in sorted(set(base_counts) | set(cur_counts)):
                base_n, cur_n = base_counts.get(kind, 0), cur_counts.get(kind, 0)
                if cur_n > base_n:
                    findings.append(Finding(
                        rule="R013", severity=ERROR, scenario=name,
                        message=f"comms regression: {cur_n} {kind}@{layer} vs "
                                f"baseline {base_n} — new collectives appeared",
                        location=f"counts:{layer}:{kind}"))
        # an inventory LAYER the baseline has no entry for (e.g. the
        # baseline was banked with --no-compile) can't be ratcheted —
        # surface it instead of silently skipping
        for layer in sorted(set(cost.inventory) - set(entry.get("collective_counts") or {})):
            if cost.counts(layer):
                findings.append(Finding(
                    rule="R013", severity=INFO, scenario=name,
                    message=f"{layer}-layer inventory has no baseline entry — "
                            f"bank with --cost --update-baseline",
                    location=f"counts:{layer}"))
    return findings


# ---------------------------------------------------------------------------
def run_cost_rules(program: ProgramInfo, cost: CostInfo,
                   analyzer: Optional[ProgramAnalyzer] = None) -> List[Finding]:
    """R009-R012 for one program (R013 is cross-program: see
    :func:`r013_cost_ratchet`)."""
    findings: List[Finding] = []
    findings.extend(r009_collective_signature(program, cost))
    findings.extend(r010_activation_budget(program, cost))
    findings.extend(r011_redundant_collectives(program, cost, analyzer))
    findings.extend(r012_host_transfer_bytes(program, cost, analyzer))
    return findings


def static_price_from_jaxpr(closed_jaxpr, metadata: Optional[Dict] = None,
                            name: str = "program",
                            kind: str = "train_step") -> Dict[str, Any]:
    """Jaxpr-only static price of ONE closed jaxpr — no StableHLO
    lowering (the same fast path graft-search prices candidates on):
    ``flops_proxy`` (trip-count-weighted dot FLOPs), liveness
    ``peak_bytes``/``peak_transient_bytes``, analytic jaxpr-layer
    ``bytes_moved``, and the eqn count (the R015 identity metric). The
    shared pricer behind the training run header AND the serving
    scheduler's program price — both stamp this dict so graft-calibrate
    fits every scope in the same units."""
    from deepspeed_tpu.analysis.search import flops_proxy

    metadata = metadata or {}
    info = ProgramInfo(name=name, jaxpr=closed_jaxpr, kind=kind,
                       metadata=metadata)
    mem = estimate_memory(info)
    analyzer = ProgramAnalyzer(info)
    ops = hlo_cost.jaxpr_collectives(analyzer, metadata.get("mesh_axes"))
    inv = hlo_cost.inventory(ops)
    return {"flops_proxy": int(flops_proxy(closed_jaxpr)),
            "peak_bytes": int(mem.peak_bytes),
            "peak_transient_bytes": int(mem.peak_transient_bytes),
            "bytes_moved": int(sum(e["bytes_moved"] for e in inv.values())),
            "eqns": int(mem.eqns)}


def static_price_from_programs(programs: Dict) -> Dict[str, Any]:
    """The step program's static price from a prior
    ``engine.traced_programs(batch, lower=False)`` result. This is what
    the telemetry run header stamps so every run's JSONL carries the
    prediction its drift events are measured against."""
    step = programs["train_step"]
    return static_price_from_jaxpr(step["jaxpr"], metadata=step["metadata"],
                                   name="engine_train_step", kind="train_step")
