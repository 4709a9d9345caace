"""The tier-1 graft-search gate: the tiny ``gpt2_test_gate`` space priced
in-process — enumeration is deterministic (two runs, identical frontier
JSON), the COMMITTED ``analysis_results/search_pareto.json`` passes R014
clean against a fresh pricing, an injected price-drift fixture fails
``tools/graft_lint.py --cost`` with rc 1, and the committed 350m_judged
artifact has the shape the next chip window consumes (>=24 candidates,
dominated-candidate provenance, frontier-generated ladder rungs). Plus
the registry-generated rule-table drift guards (R014 visible in --list,
README table in sync)."""

import copy
import importlib.util
import json
import os

import pytest

from deepspeed_tpu import analysis
from deepspeed_tpu.parallel.topology import set_topology

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
ARTIFACT = os.path.join(REPO, "analysis_results", "search_pareto.json")


@pytest.fixture(autouse=True)
def _clean():
    set_topology(None)
    yield
    set_topology(None)


@pytest.fixture(scope="module")
def gate_run():
    """One pricing of the gate space shared across the module (each
    candidate costs an engine build + trace)."""
    set_topology(None)
    out = analysis.run_space("gpt2_test_gate")
    set_topology(None)
    return out


@pytest.fixture(scope="module")
def graft_lint():
    spec = importlib.util.spec_from_file_location(
        "graft_lint_search", os.path.join(REPO, "tools", "graft_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_enumeration_and_pricing_deterministic(gate_run):
    """Two runs of unchanged code produce byte-identical frontier JSON —
    the property that makes the committed artifact a ratchet instead of
    a snapshot."""
    again = analysis.run_space("gpt2_test_gate")
    assert (json.dumps(gate_run, sort_keys=True)
            == json.dumps(again, sort_keys=True))
    assert gate_run["frontier"], "empty frontier would gate nothing"


def test_committed_artifact_passes_r014_clean(gate_run):
    artifact = analysis.load_search_artifact(ARTIFACT)
    assert "gpt2_test_gate" in artifact["spaces"], "gate space not banked"
    findings = analysis.r014_search_frontier(artifact,
                                             {"gpt2_test_gate": gate_run})
    errors = [f for f in findings if f.severity == analysis.ERROR]
    assert not errors, [f.message for f in errors]


def test_price_drift_fixture_fails_rc_1(graft_lint, gate_run, tmp_path):
    """A committed winner whose banked price is 25% off the re-priced
    truth must fail the --cost gate (the 'banked TFLOPS from a program
    that no longer exists' failure mode)."""
    artifact = copy.deepcopy(analysis.load_search_artifact(ARTIFACT))
    space = artifact["spaces"]["gpt2_test_gate"]
    winner = space["frontier"][0]
    m = space["candidates"][winner]["metrics"]
    m["peak_transient_bytes"] = int(m["peak_transient_bytes"] * 1.25)
    fixture = tmp_path / "search_pareto.json"
    fixture.write_text(json.dumps(artifact))
    rc = graft_lint.run(["--cost", "--scenarios", "moe_top1_route", "--no-ast",
                         "--search", "--search-pareto", str(fixture),
                         "--out", str(tmp_path), "-q"])
    assert rc == 1
    report = json.loads(next(tmp_path.glob("lint_*.json")).read_text())
    hits = report["programs"]["search:gpt2_test_gate"]["summary"]["rule_hits"]
    assert hits.get("R014")


def test_candidate_set_drift_is_an_error(gate_run):
    """Removing a banked candidate (as a changed axis declaration would)
    gates — the committed Pareto set must cover the declared space."""
    artifact = copy.deepcopy(analysis.load_search_artifact(ARTIFACT))
    space = artifact["spaces"]["gpt2_test_gate"]
    victim = next(c for c in space["candidates"] if c not in space["frontier"])
    del space["candidates"][victim]
    findings = analysis.r014_search_frontier(artifact,
                                             {"gpt2_test_gate": gate_run})
    errors = [f for f in findings if f.severity == analysis.ERROR]
    assert errors and "candidates drifted" in errors[0].message


def test_committed_350m_artifact_shape():
    """The judged-config entry the chip window consumes: >=24 candidates
    (acceptance), a non-trivial frontier, dominated-candidate provenance
    pointing at frontier members, knob evidence present, and a space
    signature matching the CURRENT declaration (a silently edited space
    cannot keep consuming a stale artifact)."""
    artifact = analysis.load_search_artifact(ARTIFACT)
    space = artifact["spaces"]["350m_judged"]
    cands, frontier = space["candidates"], space["frontier"]
    assert len(cands) >= 24
    assert 1 <= len(frontier) < len(cands)
    assert space["space_sig"] == analysis.SPACES["350m_judged"].signature()
    for cid, entry in cands.items():
        assert entry["metrics"]["peak_transient_bytes"] > 0
        assert entry["metrics"]["flops_proxy"] > 0
        if cid not in frontier:
            doms = entry["dominated_by"]
            assert doms and all(d in frontier for d in doms)
    # the frontier spans the remat trade: its transient floor undercuts
    # every dominated no-remat candidate by >2x (the statically-proven
    # win the window no longer has to measure losers to see)
    t_front = min(cands[c]["metrics"]["peak_transient_bytes"] for c in frontier)
    t_none = max(cands[c]["metrics"]["peak_transient_bytes"] for c in cands)
    assert t_none > 2 * t_front
    # trace evidence rode along: a rematted winner shows remat2 coverage
    rematted = [c for c in frontier if cands[c]["knobs"]["remat"] != "none"]
    assert rematted and all(cands[c]["evidence"]["remat2_sites"] > 0
                            for c in rematted)


# ---------------------------------------------------------------------------
# registry-generated docs (the R013-stops-here satellite)
# ---------------------------------------------------------------------------
def test_rule_registry_includes_r014_and_list_prints_it(graft_lint, capsys):
    assert "R014" in analysis.RULES
    rc = graft_lint.run(["--list"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "R014" in out and "gpt2_test_gate" in out


def test_readme_rule_table_generated_from_registry():
    """Every row of the registry-generated table must appear verbatim in
    README.md — a new rule without regenerated docs fails here, so the
    table can never stop at R013 (or R014) again."""
    with open(os.path.join(REPO, "README.md")) as fh:
        readme = fh.read()
    for line in analysis.rules_markdown().splitlines():
        assert line in readme, f"README rule table out of date; regenerate with " \
                               f"`python tools/graft_lint.py --rules-md`: missing {line!r}"
