"""The readers of ``tick_ahead_pct_*`` (ISSUE 35) on made-up counters:
programs dispatched while another was in flight over all programs
dispatched, nothing where the program counts no ``ticks_dispatched`` (the
parent, which reads every tick before it dispatches the next), and one entry
appended to the manifest for each serve cell but the long-document one, whose
test holds it to PR 32's seventeen metrics."""

import pytest

from benchmarks.lib import harness, program_spans

CELLS = {"chat": ("serve-gpt2-medium-chat", "itl_p95_ms"),
         "sat": ("serve-gpt2-medium-docs-sat", "serve_total_tok_s"),
         "agent": ("serve-olmoe-1b-7b-agent-sat", "serve_total_tok_s"),
         "reason": ("serve-nemotron-3-super-reason-sat", "serve_total_tok_s")}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("suffix", CELLS)
@pytest.mark.parametrize("counters, want", [
    # a saturated cell: the first program of the run, and one after each of set-up's two drains
    ({"ticks_dispatched": 3400, "ticks_dispatched_ahead": 3397, "ticks_settled": 2}, 100.0 * 3397 / 3400),
    # a scheduler that reads every program in its own step (a drafter, the prefix cache)
    ({"ticks_dispatched": 500, "ticks_dispatched_ahead": 0, "ticks_settled": 500}, 0.0),
    # the parent: no such counter
    ({"prefill_positions_fed": 4500, "decode_slots_fed": 100}, None),
    ({}, None),
])
def test_tick_ahead_on_made_up_counters(monkeypatch, manifest, suffix, counters, want):
    name = f"tick_ahead_pct_{suffix}"
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    got = module.read({"cell": harness.Cell(harness.REPO_ROOT, manifest, CELLS[suffix][0])})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("suffix", CELLS)
def test_the_manifest_names_one_for_each_of_four_serve_cells(manifest, suffix):
    cell, moves = CELLS[suffix]
    name = f"tick_ahead_pct_{suffix}"
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "serving scheduler", "moves": moves, "workloads": [cell]}
    moved, = [m for m in manifest["end_to_end"] if m["name"] == moves]
    assert cell in moved["workloads"]
    # appended, in the cells' order, behind what the manifest had
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("tick_ahead_pct_chat")
    assert names[first - 1] == "setup_import_s"
    assert names[first:first + len(CELLS)] == [f"tick_ahead_pct_{s}" for s in CELLS]
