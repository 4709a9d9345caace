"""graft-lint: rule-based static analysis over traced programs.

Walks closed jaxprs (recursing into pjit/scan/remat/custom_vjp
sub-jaxprs) and lowered StableHLO, plus a source-level AST pass, against
a registry of named rules (R001..R008) that encode this repo's
perf/determinism invariants — dense-MoE-route absence, precision
hygiene on the parity path, no host transfers in jitted steps, donation
hygiene, recompile hazards, sharding coverage, owned_device_put. CLI:
``tools/graft_lint.py``; scenario matrix: :mod:`.scenarios`; gate
semantics: :mod:`.report`.

Quick in-test usage (what tests/unit/moe/test_moe_routing.py's R001
migration calls)::

    from deepspeed_tpu.analysis import check_program
    findings = check_program(jaxpr, rules=["R001"],
                             metadata={"moe_sec": [(S, E, C)]})
"""

from deepspeed_tpu.analysis.core import (ERROR, INFO, RULES, WARN, Finding, Rule, Waiver,
                                         apply_waivers, ast_rules, cost_rules,
                                         load_waivers, program_rules)
from deepspeed_tpu.analysis.program import (ProgramAnalyzer, ProgramInfo, aval_bytes,
                                            run_program_rules)
from deepspeed_tpu.analysis import rules as _rules  # noqa: F401 — registers R001-R007
from deepspeed_tpu.analysis import source_rules as _source_rules  # noqa: F401 — registers R008
from deepspeed_tpu.analysis.memory import MemoryEstimate, estimate_memory
from deepspeed_tpu.analysis.cost import (CostInfo, build_cost, cost_baseline_from,
                                         load_cost_baseline, r013_cost_ratchet,
                                         run_cost_rules, static_price_from_jaxpr,
                                         static_price_from_programs)  # registers R009-R013
from deepspeed_tpu.analysis.search import (SPACES, Candidate, SearchSpace,
                                           enumerate_candidates, flops_proxy,
                                           gate_space_names, load_search_artifact,
                                           pareto, price_candidate,
                                           r014_search_frontier, run_space,
                                           search_artifact_from,
                                           verify_spaces)  # registers R014
from deepspeed_tpu.analysis.calibrate import (CalibrationError, calibrated_seconds,
                                              calibration_entry, calibration_from,
                                              collect_samples,
                                              default_calibration_path, fit_entry,
                                              fit_groups, load_calibration,
                                              naive_seconds, r016_calibration_drift,
                                              residual_summary,
                                              verify_calibration)  # registers R016
from deepspeed_tpu.analysis.report import (baseline_from, build_report, load_baseline,
                                           matrix_signature, new_errors, rules_markdown,
                                           summarize, write_report)

__all__ = [
    "ERROR", "WARN", "INFO", "RULES", "Finding", "Rule", "Waiver",
    "apply_waivers", "load_waivers", "program_rules", "ast_rules", "cost_rules",
    "ProgramAnalyzer", "ProgramInfo", "aval_bytes", "run_program_rules",
    "check_program",
    "MemoryEstimate", "estimate_memory",
    "CostInfo", "build_cost", "run_cost_rules", "r013_cost_ratchet",
    "load_cost_baseline", "cost_baseline_from",
    "static_price_from_jaxpr", "static_price_from_programs",
    "SPACES", "Candidate", "SearchSpace", "enumerate_candidates", "flops_proxy",
    "gate_space_names", "load_search_artifact", "pareto", "price_candidate",
    "r014_search_frontier", "run_space", "search_artifact_from", "verify_spaces",
    "CalibrationError", "calibrated_seconds", "calibration_entry",
    "calibration_from", "collect_samples", "default_calibration_path",
    "fit_entry", "fit_groups", "load_calibration", "naive_seconds",
    "r016_calibration_drift", "residual_summary", "verify_calibration",
    "baseline_from", "build_report", "load_baseline", "matrix_signature",
    "new_errors", "rules_markdown", "summarize", "write_report",
]


def check_program(jaxpr=None, rules=None, metadata=None, name="adhoc",
                  hlo_text=None, kind="fwd_bwd"):
    """One-call rule check over a single traced program — the in-test
    entry point. Returns the findings list (empty == clean)."""
    info = ProgramInfo(name=name, jaxpr=jaxpr, hlo_text=hlo_text, kind=kind,
                       metadata=metadata)
    findings, _ = run_program_rules(info, rules=rules)
    return findings
