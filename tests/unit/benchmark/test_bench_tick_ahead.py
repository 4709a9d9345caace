"""The readers of ``tick_ahead_pct_*`` (ISSUE 35) on made-up counters:
programs dispatched while another was in flight over all programs
dispatched, nothing where the program counts no ``ticks_dispatched`` (the
parent, which reads every tick before it dispatches the next), and an entry
in the manifest for every serve cell but the long-document one (no run of
that cell has read the metric): chat's moves ``itl_p95_ms``, the four saturated
cells are listed by the one entry that moves ``serve_total_tok_s``."""

import pytest

from benchmarks.lib import harness, program_spans

# cell -> (its name, the end-to-end metric its entry moves, the entry)
CELLS = {"chat": ("serve-gpt2-medium-chat", "itl_p95_ms", "tick_ahead_pct_chat"),
         "sat": ("serve-gpt2-medium-docs-sat", "serve_total_tok_s", "tick_ahead_pct_sat"),
         "agent": ("serve-olmoe-1b-7b-agent-sat", "serve_total_tok_s", "tick_ahead_pct_sat"),
         "reason": ("serve-nemotron-3-super-reason-sat", "serve_total_tok_s", "tick_ahead_pct_sat"),
         "longctx": ("serve-dots3-note-prev-longctx-sat", "serve_total_tok_s", "tick_ahead_pct_sat")}


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
    cell, _, name = CELLS[suffix]
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    got = module.read({"cell": harness.Cell(harness.REPO_ROOT, manifest, cell)})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("suffix", CELLS)
def test_the_manifest_names_one_for_each_serve_cell_that_reads_it(manifest, suffix):
    cell, moves, name = CELLS[suffix]
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "serving scheduler", "moves": moves, "workloads": entry["workloads"]}
    assert cell in entry["workloads"]
    assert entry in harness.Cell(harness.REPO_ROOT, manifest, cell).per_layer
    moved, = [m for m in manifest["end_to_end"] if m["name"] == moves]
    assert cell in moved["workloads"]
    # one entry an end-to-end metric, whatever else the manifest holds and wherever
    moved = [m["moves"] for m in manifest["per_layer"] if m["name"].startswith("tick_ahead_pct")]
    assert len(set(moved)) == len(moved)
