"""The tier-1 lint gate: tools/graft_lint.py run in-process against the
COMMITTED baseline (analysis_results/baseline.json), so every `-m "not
slow"` run enforces the rule set without a separate CI system. CPU-only,
trace-only, scenario-subset invocations keep it fast."""

import importlib.util
import json
import os
import sys

import pytest

from deepspeed_tpu.analysis.scenarios import SCENARIO_CONFIG
from deepspeed_tpu.parallel.topology import set_topology

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def graft_lint():
    spec = importlib.util.spec_from_file_location(
        "graft_lint", os.path.join(REPO, "tools", "graft_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean():
    set_topology(None)
    yield
    set_topology(None)


def test_committed_baseline_exists_and_is_clean():
    """The repo ships a CLEAN baseline: the ratchet starts at zero
    acknowledged ERRORs, so ANY new ERROR gates immediately."""
    path = os.path.join(REPO, "analysis_results", "baseline.json")
    with open(path) as fh:
        baseline = json.load(fh)
    assert baseline["version"] == 1
    assert baseline["fingerprints"] == {}


def test_gate_passes_on_clean_scenarios(graft_lint, tmp_path):
    rc = graft_lint.run(["--scenarios", "moe_top1_route,moe_top2_route",
                         "--out", str(tmp_path), "-q"])
    assert rc == 0
    reports = list(tmp_path.glob("lint_*.json"))
    assert len(reports) == 1
    report = json.loads(reports[0].read_text())
    assert report["summary"]["clean"] is True
    assert set(report["programs"]) == {"moe_top1_route", "moe_top2_route"}


def test_gate_fails_on_seeded_dense_regression(graft_lint, tmp_path, monkeypatch):
    """The ISSUE 7 acceptance check: the MoE scenario built with the dense
    route, analyzed against the clean committed baseline, exits non-zero."""
    monkeypatch.setitem(SCENARIO_CONFIG, "moe_route", "dense")
    rc = graft_lint.run(["--scenarios", "moe_top1_route",
                         "--out", str(tmp_path), "-q"])
    assert rc == 1
    report = json.loads(next(tmp_path.glob("lint_*.json")).read_text())
    assert report["programs"]["moe_top1_route"]["summary"]["rule_hits"].get("R001")
    assert report["summary"]["clean"] is False


def test_ast_pass_is_clean_against_waivers(graft_lint, tmp_path):
    """The source tree itself must stay R008-clean: every raw device_put
    is either fixed (owned_device_put) or carries an audited inline
    waiver."""
    rc = graft_lint.run(["--ast-only", "--out", str(tmp_path), "-q"])
    assert rc == 0
    report = json.loads(next(tmp_path.glob("lint_*.json")).read_text())
    s = report["ast"]["summary"]
    assert s["errors"] == 0
    # the audited waivers are present, not silently skipped
    assert s["waived"] >= 15


def test_report_findings_carry_fingerprints(graft_lint, tmp_path, monkeypatch):
    monkeypatch.setitem(SCENARIO_CONFIG, "moe_route", "dense")
    graft_lint.run(["--scenarios", "moe_top1_route", "--out", str(tmp_path), "-q"])
    report = json.loads(next(tmp_path.glob("lint_*.json")).read_text())
    for f in report["findings"]:
        assert f["fingerprint"] and f["rule"].startswith("R")


def test_update_baseline_roundtrip(graft_lint, tmp_path, monkeypatch):
    """--update-baseline acknowledges current ERRORs; an immediately
    following gate run against that baseline passes even with the
    regression still in place (the ratchet semantics)."""
    monkeypatch.setitem(SCENARIO_CONFIG, "moe_route", "dense")
    baseline = tmp_path / "baseline.json"
    rc = graft_lint.run(["--scenarios", "moe_top1_route", "--no-ast",
                         "--baseline", str(baseline), "--out", str(tmp_path),
                         "--update-baseline", "-q"])
    assert rc == 0
    acknowledged = json.loads(baseline.read_text())["fingerprints"]
    assert acknowledged
    rc = graft_lint.run(["--scenarios", "moe_top1_route", "--no-ast",
                         "--baseline", str(baseline), "--out", str(tmp_path), "-q"])
    assert rc == 0
