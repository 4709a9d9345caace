"""Clean-program matrix: every tier-1 scenario program must produce ZERO
unwaived findings (the false-positive budget is zero), and the seeded
dense-route regression (the scenario's model configuration patched) must
light R001 up."""

import pytest

from deepspeed_tpu.analysis import run_program_rules, summarize
from deepspeed_tpu.analysis import scenarios as scen
from deepspeed_tpu.parallel.topology import set_topology


@pytest.fixture(autouse=True)
def _clean():
    set_topology(None)
    yield
    set_topology(None)


@pytest.fixture(scope="module")
def matrix():
    """Build the full matrix once per module (trace-only, but engine
    construction isn't free)."""
    set_topology(None)
    programs, skipped = scen.build()
    set_topology(None)
    return {p.name: p for p in programs}, skipped


def test_matrix_builds_expected_scenarios(matrix):
    programs, skipped = matrix
    expected = {"gpt2_fwd_bwd", "llama_fwd_bwd", "bert_fwd_bwd",
                "moe_top1_route", "moe_top2_route", "train_batch_parity",
                "zero2_train_step", "zero3_train_step", "moe_ep_step",
                "pipe_chunked_step", "pipe_1f1b_step", "serve_decode_step",
                "rlhf_rollout_step"}
    assert expected <= set(programs) | set(skipped)
    # the 16-device composition is allowed to skip on an 8-device runtime
    # — never to silently vanish: the skip reasons inventory the gaps
    for gap in ("pipe_scan_step", "composition_3d_ep_zeropp"):
        assert gap in set(programs) | set(skipped)


def test_cost_signature_metadata_armed(matrix):
    """The cost-rule metadata must actually arrive — a typo would
    silently disarm R009/R010 the same way a parity typo would disarm
    R002/R005."""
    programs, _ = matrix
    if "pipe_chunked_step" in programs:
        meta = programs["pipe_chunked_step"].metadata
        assert meta.get("activation_budget_bytes", 0) > 0
        assert any(e["kind"] == "collective_permute"
                   for e in meta["collective_signature"])
    if "pipe_1f1b_step" in programs:
        meta = programs["pipe_1f1b_step"].metadata
        assert meta["pipe_schedule"]["schedule"] == "1f1b"
        assert meta["pipe_schedule"]["stash_slots"] == 2
        assert meta.get("activation_budget_bytes", 0) > 0
        # the tightened bound must undercut the chunked scenario's budget
        if "pipe_chunked_step" in programs:
            assert (meta["activation_budget_bytes"]
                    < programs["pipe_chunked_step"].metadata["activation_budget_bytes"])
        assert any(e["kind"] == "collective_permute" and e["count"] == 4
                   for e in meta["collective_signature"])
    for name in ("zero2_train_step", "zero3_train_step"):
        if name in programs:
            meta = programs[name].metadata
            assert meta["zero_stage"] in (2, 3)
            kinds = {e["kind"] for e in meta["collective_signature"]}
            assert {"all_gather", "reduce_scatter"} <= kinds
    if "moe_ep_step" in programs:
        kinds = {e["kind"] for e in programs["moe_ep_step"].metadata["collective_signature"]}
        assert {"dense_dispatch", "resharding"} <= kinds
    if "serve_decode_step" in programs:
        # the graft-serve decode tick (PR 14): budget armed for R010, the
        # tp=2 serving collective signature pinned for R009
        meta = programs["serve_decode_step"].metadata
        assert meta.get("activation_budget_bytes", 0) > 0
        assert any(e["kind"] == "all_reduce" and e["count"] == 5
                   for e in meta["collective_signature"])


def test_clean_matrix_zero_false_positives(matrix):
    """Every scenario program the repo ships must be lint-clean — a rule
    that cries wolf on the programs we actually run is worse than no
    rule."""
    programs, _ = matrix
    dirty = {}
    for name, info in programs.items():
        findings, _ = run_program_rules(info)
        bad = [f for f in findings if not f.waived]
        if bad:
            dirty[name] = [(f.rule, f.message) for f in bad]
    assert not dirty, f"false positives on clean programs: {dirty}"


def test_train_batch_parity_metadata_armed(matrix):
    """The parity scenario must actually arm the rules the ROADMAP cares
    about — a metadata typo would silently disarm R002/R005."""
    programs, _ = matrix
    info = programs["train_batch_parity"]
    assert info.metadata["parity"] is True
    assert info.metadata["expect_donation"] is True
    assert info.hlo_text and ("tf.aliasing_output" in info.hlo_text
                              or "jax.buffer_donor" in info.hlo_text)


def test_moe_scenarios_declare_sec_signature(matrix):
    programs, _ = matrix
    for name in ("moe_top1_route", "moe_top2_route"):
        sigs = programs[name].metadata["moe_sec"]
        assert sigs and all(len(s) == 3 for s in sigs)


def test_skipped_scenarios_are_structured_gaps(matrix):
    """Every skip carries a machine-readable blocking gap {kind, detail}
    — the shape the lint report commits, so burn-down is a metric."""
    _, skipped = matrix
    for name, gap in skipped.items():
        assert set(gap) == {"kind", "detail"}, (name, gap)
        assert gap["kind"] and gap["detail"]


def test_composition_blocking_gap_ratchet():
    """ROADMAP-5 burn-down, step 2: the composition scenario's first
    blocking gap may only move FORWARD through the order
    device-count -> partial-manual -> moe-in-pipe -> none. The
    device-count link is burned down (a <16-device run probes the
    16-virtual-device build in a subprocess and reports the gap behind
    it), so the floor is now moe-in-pipe — TIGHTER than the PR-12 floor,
    regardless of the ambient device count."""
    from deepspeed_tpu.analysis.scenarios import (COMPOSITION_GAP_ORDER,
                                                  composition_blocking_gap,
                                                  composition_gap_rank)
    import pytest

    gap = composition_blocking_gap()
    assert gap["kind"] in COMPOSITION_GAP_ORDER, gap
    if gap.get("probe") == "failed":
        # the floor depends on the 16-device subprocess probe; a rig where
        # the probe itself cannot run (resource-starved, fork-limited) is
        # an environment problem, not a burn-down regression
        pytest.skip(f"16-device composition probe failed on this rig: {gap}")
    floor = "moe_in_pipe"
    assert composition_gap_rank(gap["kind"]) >= composition_gap_rank(floor), (
        f"composition gap regressed backward: {gap} (floor: "
        f"{floor})")


def test_dense_env_route_fires_r001_through_scenarios(monkeypatch):
    """The dense route — the seeded regression — patched into the
    scenarios' configuration must reach the traced program and produce
    ERROR-severity R001 findings."""
    monkeypatch.setitem(scen.SCENARIO_CONFIG, "moe_route", "dense")
    programs, _ = scen.build(["moe_top1_route", "moe_top2_route"])
    assert len(programs) == 2
    for info in programs:
        findings, _ = run_program_rules(info, rules=["R001"])
        s = summarize(findings)
        assert s["errors"] > 0, f"{info.name} did not fire R001 under dense route"
