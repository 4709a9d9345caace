"""Report + baseline layer: JSON emission and the CI gate semantics.

``lint_<sig>.json`` is the evidence artifact (``analysis_results/``,
next to the autotuner's winner files): per-program rule hit counts,
waivers in effect, precision attribution, and every finding with its
stable fingerprint. The committed ``baseline.json`` holds the set of
acknowledged ERROR fingerprints; the CLI exits non-zero only on *new*
unwaived ERRORs, so the gate can hold the line while known debt is
burned down explicitly (same contract as a ratcheting type-checker)."""

import hashlib
import json
import os
import time
from typing import Dict, Iterable, List, Optional, Tuple

from deepspeed_tpu.analysis.core import ERROR, Finding

BASELINE_VERSION = 1


def matrix_signature(program_names: Iterable[str]) -> str:
    """Short stable id for 'which matrix produced this report' — the
    report filename key, so reports from different scenario subsets
    don't overwrite each other."""
    import jax
    raw = ",".join(sorted(program_names)) + "|" + jax.__version__
    return hashlib.sha1(raw.encode()).hexdigest()[:10]


def summarize(findings: List[Finding]) -> Dict:
    """Rule hit counts split by status (rule_hits / waived / errors /
    clean)."""
    hits: Dict[str, int] = {}
    waived = errors = 0
    for f in findings:
        hits[f.rule] = hits.get(f.rule, 0) + 1
        if f.waived:
            waived += 1
        elif f.severity == ERROR:
            errors += 1
    return {"rule_hits": dict(sorted(hits.items())), "waived": waived,
            "errors": errors, "clean": errors == 0}


def build_report(per_program: Dict[str, Tuple[List[Finding], Dict]],
                 ast_findings: List[Finding],
                 skipped: Optional[Dict[str, str]] = None,
                 waivers_in_effect: Optional[List[dict]] = None,
                 cost_by_program: Optional[Dict] = None,
                 stale_waivers: Optional[List[dict]] = None) -> Dict:
    import jax
    all_findings = [f for fs, _ in per_program.values() for f in fs] + list(ast_findings)
    report = {
        "tool": "graft-lint",
        "version": BASELINE_VERSION,
        "jax_version": jax.__version__,
        "generated_unix": int(time.time()),
        "programs": {
            name: {"summary": summarize(fs), "metrics": metrics}
            for name, (fs, metrics) in per_program.items()
        },
        "ast": {"summary": summarize(list(ast_findings))},
        # structured blocking gaps ({"kind", "detail"} per skipped
        # scenario, scenarios.ScenarioSkipped.kind): the composition
        # scenario's first blocking gap is a ratchetable metric here, not
        # a prose string (ROADMAP-5 burn-down)
        "skipped_scenarios": dict(skipped or {}),
        "waivers_in_effect": list(waivers_in_effect or []),
        # waivers that covered no current finding: dead acknowledgements
        # to prune, surfaced as WARNs by the CLI (never gating)
        "stale_waivers": list(stale_waivers or []),
        "summary": summarize(all_findings),
        "findings": [f.to_dict() for f in all_findings],
    }
    if cost_by_program is not None:
        # the --cost pass: per-program static memory estimate + collective
        # inventory + backend cross-check (analysis/cost.py)
        report["cost"] = {name: cost.to_dict()
                          for name, cost in sorted(cost_by_program.items())}
    return report


def write_report(report: Dict, out_dir: str, sig: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"lint_{sig}.json")
    with open(path, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=False)
        fh.write("\n")
    return path


def rules_markdown() -> str:
    """The README rule table, generated FROM the registry (``graft_lint
    --rules-md``). The README embeds this output verbatim and a tier-1
    test asserts every registry row is present, so a new rule can never
    ship with stale docs again (the R013 drift this replaced)."""
    from deepspeed_tpu.analysis.core import RULES
    lines = ["| rule | severity | layer | what it gates |",
             "|------|----------|-------|---------------|"]
    for r in sorted(RULES.values(), key=lambda r: r.id):
        lines.append(f"| {r.id} | {r.severity} | {r.layer} | {r.title} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
def load_baseline(path: str) -> Dict:
    if not os.path.exists(path):
        return {"version": BASELINE_VERSION, "fingerprints": {}}
    with open(path) as fh:
        baseline = json.load(fh)
    if baseline.get("version") != BASELINE_VERSION:
        raise ValueError(f"baseline {path} has version {baseline.get('version')}, "
                         f"expected {BASELINE_VERSION} — regenerate with --update-baseline")
    return baseline


def baseline_from(findings: Iterable[Finding]) -> Dict:
    """A baseline acknowledging every current UNWAIVED ERROR — the
    ratchet's starting tooth. Waived findings are already acknowledged by
    their waiver (which travels with the code/config) and must not also
    occupy a baseline slot a future unwaived finding could hide behind."""
    fps = {}
    for f in findings:
        if f.severity == ERROR and not f.waived:
            fps[f.fingerprint()] = {"rule": f.rule, "scenario": f.scenario,
                                    "message": f.message}
    return {"version": BASELINE_VERSION, "fingerprints": dict(sorted(fps.items()))}


def new_errors(findings: Iterable[Finding], baseline: Dict) -> List[Finding]:
    """The gate: unwaived ERROR findings whose fingerprint the baseline
    does not acknowledge."""
    known = set(baseline.get("fingerprints", {}))
    return [f for f in findings
            if f.severity == ERROR and not f.waived and f.fingerprint() not in known]
