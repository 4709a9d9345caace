"""The tier-1 cost gate: tools/graft_lint.py --cost run in-process against
the COMMITTED cost baseline (analysis_results/cost_baseline.json) on a
CPU-fast scenario subset, including the deliberate-regression exit-1
cases — the dense MoE route patched into the scenario (R009 route-signature drift + the
einsum route delta inventoried in the cost report) and an activation
budget below the chunked pipe schedule's static estimate (R010, the
pre-wired ROADMAP-2 1F1B gate). Plus the stale-waiver WARN units."""

import importlib.util
import json
import os

import pytest

from deepspeed_tpu.analysis.core import Finding, Waiver, stale_config_waivers
from deepspeed_tpu.analysis.scenarios import SCENARIO_CONFIG
from deepspeed_tpu.parallel.topology import set_topology

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def graft_lint():
    spec = importlib.util.spec_from_file_location(
        "graft_lint_cost", os.path.join(REPO, "tools", "graft_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean():
    os.environ.pop("DS_PIPE_ACT_BUDGET_MB", None)
    set_topology(None)
    yield
    os.environ.pop("DS_PIPE_ACT_BUDGET_MB", None)
    set_topology(None)


def _report(tmp_path):
    return json.loads(next(tmp_path.glob("lint_*.json")).read_text())


def test_committed_cost_baseline_covers_the_matrix():
    path = os.path.join(REPO, "analysis_results", "cost_baseline.json")
    with open(path) as fh:
        baseline = json.load(fh)
    assert baseline["version"] == 1
    programs = baseline["programs"]
    # the gate scenarios must be banked or the ratchet has no teeth
    for name in ("moe_ep_step", "pipe_chunked_step", "pipe_1f1b_step",
                 "zero3_train_step", "train_batch_parity",
                 "serve_decode_step", "serve_quant_decode_step",
                 "rlhf_rollout_step", "reshard_resume"):
        assert name in programs, name
        assert programs[name]["peak_bytes"] > 0
        assert "collective_counts" in programs[name]
    # the elastic restore path's gather bytes are ratcheted (graft-elastic):
    # the banked reshard program must carry real compiled movement and its
    # gather collectives, and no reduction may ever appear in a reshard
    reshard = programs["reshard_resume"]
    assert reshard["bytes_moved"]["compiled"] > 0
    assert reshard["collective_counts"]["compiled"]["all_gather"] >= 1
    assert "all_reduce" not in reshard["collective_counts"]["compiled"]
    # the banked serve decode tick must sit under its committed budget
    # with headroom for the ratchet to have teeth (PERF.md §PR14)
    from deepspeed_tpu.analysis.scenarios import SERVE_DECODE_BUDGET_MB
    assert (programs["serve_decode_step"]["peak_transient_bytes"]
            <= SERVE_DECODE_BUDGET_MB * 2**20)
    # graft-quant-serve's headline A/B, banked: the quantized decode tick
    # moves strictly fewer compiled wire bytes AND holds a far smaller
    # peak than the fp tick, under its own committed budget (PERF.md §PR16)
    from deepspeed_tpu.analysis.scenarios import SERVE_QUANT_DECODE_BUDGET_MB
    quant = programs["serve_quant_decode_step"]
    assert quant["bytes_moved"]["compiled"] < (
        programs["serve_decode_step"]["bytes_moved"]["compiled"])
    assert quant["peak_bytes"] < programs["serve_decode_step"]["peak_bytes"]
    assert quant["peak_transient_bytes"] <= SERVE_QUANT_DECODE_BUDGET_MB * 2**20
    assert quant["collective_counts"]["compiled"]["all_reduce"] == 5
    # exactly the two argmax gathers — one more would mean GSPMD started
    # re-gathering the int8 codes or the KV pool every tick
    assert quant["collective_counts"]["compiled"]["all_gather"] == 2
    # the banked 1F1B transient must sit strictly below both the chunked
    # schedule's transient AND its own committed budget — the ratchet-DOWN
    # this PR's schedule refactor banked (PERF.md §PR11)
    from deepspeed_tpu.analysis.scenarios import PIPE_1F1B_BUDGET_MB
    t_1f1b = programs["pipe_1f1b_step"]["peak_transient_bytes"]
    t_chunked = programs["pipe_chunked_step"]["peak_transient_bytes"]
    assert t_1f1b < t_chunked
    assert t_1f1b <= PIPE_1F1B_BUDGET_MB * 2**20 < t_chunked
    # 2 boundary hops per tick boundary across the 3 phase bodies
    assert programs["pipe_1f1b_step"]["collective_counts"]["jaxpr"][
        "collective_permute"] == 4


def test_cost_gate_passes_clean_subset(graft_lint, tmp_path):
    rc = graft_lint.run(["--cost", "--scenarios",
                         "moe_ep_step,pipe_chunked_step,pipe_1f1b_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 0
    report = _report(tmp_path)
    assert set(report["cost"]) == {"moe_ep_step", "pipe_chunked_step",
                                   "pipe_1f1b_step"}
    for name, cost in report["cost"].items():
        assert cost["memory"]["peak_bytes"] > 0
        assert cost["memory"]["peak_transient_bytes"] > 0
        assert cost["collectives"], name  # inventories present
    # the MoE EP program proves its reshard (logical a2a) sites statically
    moe = report["cost"]["moe_ep_step"]
    assert moe["collectives"]["jaxpr"]["counts"].get("resharding", 0) >= 4
    # the ZeRO reduce-scatter expectation is inventoried as unchecked on
    # CPU, never silently passed (declared backends: tpu)
    rc = graft_lint.run(["--cost", "--scenarios", "zero3_train_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 0
    report = _report(tmp_path)
    unchecked = report["cost"]["zero3_train_step"]["unchecked_signature"]
    assert any(e.get("kind") == "reduce_scatter" for e in unchecked)


def test_dense_route_regression_exits_1_with_cost_delta(graft_lint, tmp_path,
                                                        monkeypatch):
    """The EP scenario's model built with the dense route: R009 fires on
    the route-signature drift (and R001 on the [S,E,C] shape), and the cost
    report carries the dense-dispatch delta — the a2a endpoints fed by an
    einsum instead of a permutation."""
    monkeypatch.setitem(SCENARIO_CONFIG, "moe_route", "dense")
    rc = graft_lint.run(["--cost", "--scenarios", "moe_ep_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 1
    report = _report(tmp_path)
    hits = report["programs"]["moe_ep_step"]["summary"]["rule_hits"]
    assert hits.get("R009") and hits.get("R001")
    # the inventoried route delta: dense-dispatch sites appear in the
    # jaxpr-layer collective counts (0 in the committed baseline)
    counts = report["cost"]["moe_ep_step"]["collectives"]["jaxpr"]["counts"]
    assert counts.get("dense_dispatch", 0) >= 1


def test_chunked_schedule_fails_under_the_1f1b_budget(graft_lint, tmp_path,
                                                      monkeypatch):
    """The ROADMAP-2 gate, cashed in: the chunked-wave schedule forced
    under the SAME activation budget the 1F1B scenario passes must fail
    the run — the tightened bound bites."""
    from deepspeed_tpu.analysis.scenarios import PIPE_1F1B_BUDGET_MB
    monkeypatch.setenv("DS_PIPE_ACT_BUDGET_MB", str(PIPE_1F1B_BUDGET_MB))
    rc = graft_lint.run(["--cost", "--scenarios", "pipe_chunked_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 1
    report = _report(tmp_path)
    assert report["programs"]["pipe_chunked_step"]["summary"]["rule_hits"].get("R010")
    budget_msgs = [f for f in report["findings"] if f["rule"] == "R010"]
    assert budget_msgs and "budget" in budget_msgs[0]["message"]


def test_serve_wq_env_drift_exits_1(graft_lint, tmp_path, monkeypatch):
    """The quantized serving scenario built fp where int8 is banked: the
    traced program swings back to full-width fp kernels — peak bytes jump
    past the R013 ratchet tolerance. The graft-quant-serve seeded
    regression."""
    monkeypatch.setitem(SCENARIO_CONFIG, "serve_weight_dtype", "fp")
    rc = graft_lint.run(["--cost", "--scenarios", "serve_quant_decode_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 1
    report = _report(tmp_path)
    hits = report["programs"]["serve_quant_decode_step"]["summary"]["rule_hits"]
    assert hits.get("R013"), hits
    # the committed fp->int8 saving, forfeited by the drift: measured peak
    # exceeds the banked quantized peak well past tolerance
    path = os.path.join(REPO, "analysis_results", "cost_baseline.json")
    with open(path) as fh:
        banked = json.load(fh)["programs"]["serve_quant_decode_step"]
    measured = report["cost"]["serve_quant_decode_step"]["memory"]["peak_bytes"]
    assert measured > banked["peak_bytes"] * 1.05


def test_serve_quant_scenario_clean_on_committed_intent(graft_lint, tmp_path):
    """The committed int8 configuration passes the full cost gate, and the
    traced program really is the quantized one: int8 weight codes show up
    as a peak-bytes drop vs the fp serving tick, not just metadata."""
    rc = graft_lint.run(["--cost", "--scenarios", "serve_quant_decode_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 0
    report = _report(tmp_path)
    cost = report["cost"]["serve_quant_decode_step"]
    assert cost["memory"]["peak_transient_bytes"] > 0
    assert cost["collectives"]["compiled"]["counts"].get("all_reduce") == 5


def test_serve_scenario_clean_on_committed_write(graft_lint, tmp_path):
    rc = graft_lint.run(["--cost", "--scenarios", "serve_decode_step",
                         "--no-ast", "--out", str(tmp_path), "-q"])
    assert rc == 0
    report = _report(tmp_path)
    cost = report["cost"]["serve_decode_step"]
    assert cost["memory"]["peak_transient_bytes"] > 0
    # the tp=2 serving collectives are real compiled-layer ops
    assert cost["collectives"]["compiled"]["counts"].get("all_reduce") == 5


def test_cost_update_baseline_roundtrip(graft_lint, tmp_path, monkeypatch):
    """--cost --update-baseline banks the (regressed) costs into the cost
    baseline; the immediately following gate run passes — ratchet
    semantics, merge-preserving entries from other scenarios."""
    monkeypatch.setitem(SCENARIO_CONFIG, "moe_route", "dense")
    baseline = tmp_path / "baseline.json"
    cost_baseline = tmp_path / "cost_baseline.json"
    # seed the cost baseline with a foreign entry that must survive the merge
    cost_baseline.write_text(json.dumps(
        {"version": 1, "tolerance": 0.05,
         "programs": {"other_program": {"peak_bytes": 123}}}))
    rc = graft_lint.run(["--cost", "--scenarios", "moe_ep_step", "--no-ast",
                         "--baseline", str(baseline),
                         "--cost-baseline", str(cost_baseline),
                         "--out", str(tmp_path), "--update-baseline", "-q"])
    assert rc == 0
    banked = json.loads(cost_baseline.read_text())["programs"]
    assert banked["moe_ep_step"]["peak_bytes"] > 0
    assert banked["other_program"] == {"peak_bytes": 123}  # merge, not replace
    rc = graft_lint.run(["--cost", "--scenarios", "moe_ep_step", "--no-ast",
                         "--baseline", str(baseline),
                         "--cost-baseline", str(cost_baseline),
                         "--out", str(tmp_path), "-q"])
    assert rc == 0


def test_corrupt_cost_baseline_fails_loudly(graft_lint, tmp_path):
    bad = tmp_path / "cost_baseline.json"
    bad.write_text(json.dumps({"version": 1, "programs": {
        "moe_ep_step": {"peak_bytes": 1, "typo_key": 2}}}))
    with pytest.raises(ValueError, match="unknown keys"):
        graft_lint.run(["--cost", "--scenarios", "moe_top1_route", "--no-ast",
                        "--cost-baseline", str(bad),
                        "--out", str(tmp_path), "-q"])


# ---------------------------------------------------------------------------
# stale-waiver detection units
# ---------------------------------------------------------------------------
def test_stale_config_waiver_detected():
    findings = [Finding(rule="R003", severity="ERROR", scenario="train_batch_parity",
                        message="host primitive 'device_put' inside traced step")]
    live = Waiver(rule="R003", scenario="train_batch*")
    dead = Waiver(rule="R003", scenario="nonexistent_scenario")
    wrong_rule = Waiver(rule="R007", scenario="train_batch*")
    stale = stale_config_waivers(findings, [live, dead, wrong_rule])
    assert dead in stale and wrong_rule in stale and live not in stale


def test_stale_inline_waiver_detected_and_docstrings_exempt():
    import ast as ast_mod

    from deepspeed_tpu.analysis.source_rules import stale_inline_waivers

    src = (
        '"""Docs showing the syntax:\n'
        "    x = jax.device_put(y)  # graft-lint: waive R008 example only\n"
        '"""\n'
        "a = 1  # graft-lint: waive R008 covers a real finding\n"
        "b = 2  # graft-lint: waive R008 stale, nothing fires here\n"
    )
    files = [("pkg/mod.py", src, ast_mod.parse(src))]
    findings = [Finding(rule="R008", severity="ERROR", scenario="pkg/mod.py",
                        message="raw jax.device_put", location="pkg/mod.py:4",
                        waived=True)]
    stale = stale_inline_waivers(files, findings)
    assert len(stale) == 1
    assert stale[0]["line"] == 5  # the docstring example (line 2) is exempt
