"""The reader of ``moe_buffer_fill_pct_reason`` on made-up counters: the
routed rows of the prefill ticks over the rows of the buffers the held
expert layers chose for them, and nothing where the program counts no
buffer (the parent)."""

import pytest

from benchmarks.lib import harness, program_spans

CELL = "serve-nemotron-3-super-reason-sat"
NAME = "moe_buffer_fill_pct_reason"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("counters, want", [
    # 54 prefill ticks x 5 layers of ~8,700 held rows in the 11,264-row buffer
    ({"moe_rows_routed_prefill": 54 * 5 * 8700, "moe_rows_buffered_prefill": 54 * 5 * 11264},
     100.0 * 8700 / 11264),
    # every copy buffered: the layer's largest size, what the parent's layer always moved
    ({"moe_rows_routed_prefill": 8700, "moe_rows_buffered_prefill": 180224}, 100.0 * 8700 / 180224),
    ({"moe_rows_routed_prefill": 0, "moe_rows_buffered_prefill": 11264}, 0.0),
    # decode ticks' buffers are not this metric's
    ({"moe_rows_routed_prefill": 500, "moe_rows_buffered_prefill": 1000,
      "moe_rows_routed_decode": 7, "moe_rows_buffered_decode": 1408, "moe_rows_buffered": 2408}, 50.0),
    ({"moe_rows_routed_prefill": 8700, "moe_rows_routed": 9000}, None),   # the parent: no such counter
    ({}, None),                                                          # a dense model
])
def test_buffer_fill_on_made_up_counters(monkeypatch, manifest, counters, want):
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", NAME + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    got = module.read({"cell": harness.Cell(harness.REPO_ROOT, manifest, CELL)})
    assert got is None if want is None else got == pytest.approx(want)


def test_the_manifest_names_it_for_the_reasoning_cell_alone(manifest):
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "MoE layer", "moves": "serve_total_tok_s", "workloads": [CELL]}
    # wherever it stands in the list
    assert entry in harness.Cell(harness.REPO_ROOT, manifest, CELL).per_layer
