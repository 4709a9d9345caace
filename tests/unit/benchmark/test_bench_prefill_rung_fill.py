"""The readers of ``prefill_rung_fill_pct_*`` (ISSUE 33) on made-up
counters: prompt tokens fed over the positions the prefill programs that
ran computed (the rung's sequences x the chunk), nothing where the program
counts no ``prefill_positions_run`` (the parent: one size of program), and
one entry at the end of the manifest for each serve cell but the long-document
one: ``test_bench_joyai_llm_flash.py`` holds that cell to PR 32's seventeen
metrics, and a PR that claims a gain edits no file the benchmark has."""

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
    name = f"prefill_rung_fill_pct_{suffix}"
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    got = module.read({"cell": harness.Cell(harness.REPO_ROOT, manifest, CELLS[suffix][0])})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("suffix", CELLS)
def test_the_manifest_names_one_for_each_of_four_serve_cells(manifest, suffix):
    cell, moves = CELLS[suffix]
    name = f"prefill_rung_fill_pct_{suffix}"
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "serving programs", "moves": moves, "workloads": [cell]}
    moved, = [m for m in manifest["end_to_end"] if m["name"] == moves]
    assert cell in moved["workloads"]
    # appended behind PR 32's last entry, in the cells' order, nothing before them moved
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("prefill_rung_fill_pct_chat")
    assert names[first - 1] == "mla_decode_roofline_longdoc"
    assert names[first:] == [f"prefill_rung_fill_pct_{s}" for s in CELLS]
