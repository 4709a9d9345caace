"""The readers of ``prefill_rung_fill_pct_*`` (ISSUE 33) on made-up
counters: prompt tokens fed over the positions the prefill programs that
ran computed (the rung's sequences x the chunk), nothing where the program
counts no ``prefill_positions_run`` (the parent: one size of program), and
an entry in the manifest for each of the four serve cells whose prefill
program has a rung below the whole: chat's moves ``itl_p95_ms``, and the three
saturated cells are listed by the one entry that moves ``serve_total_tok_s``."""

import pytest

from benchmarks.lib import harness, program_spans

# cell -> (its name, the end-to-end metric its entry moves, the entry)
CELLS = {"chat": ("serve-gpt2-medium-chat", "itl_p95_ms", "prefill_rung_fill_pct_chat"),
         "sat": ("serve-gpt2-medium-docs-sat", "serve_total_tok_s", "prefill_rung_fill_pct_sat"),
         "agent": ("serve-olmoe-1b-7b-agent-sat", "serve_total_tok_s", "prefill_rung_fill_pct_sat"),
         "reason": ("serve-nemotron-3-super-reason-sat", "serve_total_tok_s",
                    "prefill_rung_fill_pct_sat")}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("suffix", CELLS)
@pytest.mark.parametrize("counters, want", [
    # 600 chat prefill ticks that feed ~1.5 slots of 16 tokens on the 8-slot rung of 32
    ({"prefill_positions_fed": 600 * 24, "prefill_positions_run": 600 * 8 * 16,
      "prefill_positions_computed": 600 * 32 * 16}, 100.0 * 24 / (8 * 16)),
    # every tick on the whole rung: the whole-shape fill
    ({"prefill_positions_fed": 4500, "prefill_positions_run": 8192,
      "prefill_positions_computed": 8192}, 100.0 * 4500 / 8192),
    ({"prefill_positions_fed": 0, "prefill_positions_run": 128}, 0.0),
    # the parent: one size of program, no such counter
    ({"prefill_positions_fed": 4500, "prefill_positions_computed": 8192}, None),
    ({}, None),
])
def test_rung_fill_on_made_up_counters(monkeypatch, manifest, suffix, counters, want):
    cell, _, name = CELLS[suffix]
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    got = module.read({"cell": harness.Cell(harness.REPO_ROOT, manifest, cell)})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("suffix", CELLS)
def test_the_manifest_names_one_for_each_of_four_serve_cells(manifest, suffix):
    cell, moves, name = CELLS[suffix]
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "serving programs", "moves": moves, "workloads": entry["workloads"]}
    assert cell in entry["workloads"]
    assert entry in harness.Cell(harness.REPO_ROOT, manifest, cell).per_layer
    moved, = [m for m in manifest["end_to_end"] if m["name"] == moves]
    assert cell in moved["workloads"]
    # one entry an end-to-end metric, whatever else the manifest holds and wherever
    moved = [m["moves"] for m in manifest["per_layer"] if m["name"].startswith("prefill_rung_fill_pct")]
    assert len(set(moved)) == len(moved)
