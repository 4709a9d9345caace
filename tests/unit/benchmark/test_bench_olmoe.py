"""The OLMoE cell: at the test preset through ``harness.run_cell`` on the
CPU (untraced and traced, the last line held to the contract), its readers
on made-up counters, and its operation counts against numbers worked by
hand for the published sizes. Nothing here is a measurement."""

import copy
import json
import os

import pytest

from benchmarks.lib import harness, olmoe_ticks, opcounts_olmoe, peaks, program_spans

CELL, LIKE = "t-agent", "serve-olmoe-1b-7b-agent-sat"
SEED = 2 ** 31 + 26


@pytest.fixture(scope="module")
def agent_copy(bench_copy):
    """The session's copy of the benchmark with the OLMoE test cell added to
    a manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "olmoe-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/olmoe-test.json", "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "olmoe-test", "traffic": "test-agent",
                                  "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(agent_copy):
    root, manifest = agent_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs", "olmoe-1b-7b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, agent_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(agent_copy[0], agent_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines):
    """Counters and spans of the program are read on any platform; roofline
    shares, kernel time and the idle share need a chip and are left out."""
    metrics = lines[1]["metrics"]
    # the cell's own entry, and the entries it shares with the other saturated cells
    assert {"moe_pad_pct_agent", "decode_device_wait_ms_p50_sat",
            "prefill_device_wait_ms_p50", "sched_host_ms_p50_sat", "kv_live_pct_sat",
            "slot_occupancy_pct", "prefill_fill_pct_sat", "prefill_rung_fill_pct_sat",
            "tick_ahead_pct_sat", "recompiles_in_window_sat", "setup_trace_lower_s",
            "setup_backend_load_s", "setup_cache_misses", "setup_programs_loaded",
            "setup_engine_init_s", "setup_import_s"} <= set(metrics)
    assert not {"decode_roofline_agent", "prefill_roofline_agent", "moe_kernel_time_pct_sat",
                "moe_kernel_roofline_agent", "device_idle_pct_sat"} & set(metrics)
    # grouped by expert, the buffer adds no rows: what is padding is parked
    # slots and short chunks, so the share is that of the two programs' fill
    assert 0 < metrics["moe_pad_pct_agent"]["value"] < 100
    assert metrics["recompiles_in_window_sat"]["value"] == 0


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "olmoe"
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 2048}
    assert cell.config["serve"]["max_queue"] == 2048
    assert mix["prompt_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert mix["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (mix["max_total"], mix["block"], mix["drain_s"], mix["trace_seconds"]) == (2048, 32, 0, 4)
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    for metric in cell.per_layer:
        path = os.path.join(harness.REPO_ROOT, "benchmarks", "layer_metrics", metric["name"])
        assert os.path.exists(path + ".py") or os.path.exists(path + ".json")
        assert metric["moves"] in ("serve_total_tok_s", "setup_s")


def test_moe_pad_pct_on_made_up_counters(monkeypatch):
    reader = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics",
                                 "moe_pad_pct_agent.py")
    monkeypatch.setattr(program_spans, "ring",
                        lambda: ([], {"moe_rows_routed": 600, "moe_rows_computed": 800}))
    assert reader.read({}) == pytest.approx(25.0)
    monkeypatch.setattr(program_spans, "ring", lambda: ([], {"prefill_positions_fed": 5}))
    assert reader.read({}) is None            # a dense model, or the parent commit


def test_tick_shape_and_roofline_on_made_up_counters():
    config = published()
    serve = config["serve"]
    # 100 decode ticks that fed 24 of 32 slots, 50 prefill ticks a quarter
    # full; 150 working ticks with 28 slots busy holding 1,000 positions each
    program = {"decode_slots_fed": 2400, "decode_slots_computed": 3200,
               "prefill_positions_fed": 50 * 512, "prefill_positions_computed": 50 * 32 * 64}
    run = {"slot_ticks": 150 * 32, "slot_ticks_busy": 150 * 28, "kv_positions_live": 150 * 28000}
    decode = olmoe_ticks.tick_shape("decode", program, run, serve)
    assert decode["ticks"] == 100 and decode["tokens"] == 24 and decode["sequences"] == 24
    assert decode["kv_positions"] == pytest.approx(28000 * 24 / 28)
    prefill = olmoe_ticks.tick_shape("prefill", program, run, serve)
    assert prefill["ticks"] == 50 and prefill["tokens"] == 512 and prefill["sequences"] == 8
    assert olmoe_ticks.tick_shape("decode", {}, run, serve) is None

    chip = peaks.peaks_for("TPU v5 lite")
    least, bound, flops, nbytes = olmoe_ticks.tick_least_ms(config, decode, chip)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9 * 1e3)
    # 24 tokens reach 61.4 of 64 experts: 6.18 GB of experts, 0.27 GB of attention and
    # router weights, 0.21 GB of head, 8 layers x 24,024 positions x 4,160 B = 0.80 GB of cache
    assert opcounts_olmoe.expert_bytes(config, 24) == pytest.approx(6.18e9, rel=2e-3)
    assert nbytes == pytest.approx(6.18e9 + 0.271e9 + 0.206e9 + 0.7995e9, rel=2e-3)
    assert 9.0 < least < 9.2
    # a full prefill tick is bound by compute, one a quarter full by memory
    full = dict(prefill, tokens=2048, sequences=32, kv_positions=32000)
    assert olmoe_ticks.tick_least_ms(config, full, chip)[1] == "compute"
    assert olmoe_ticks.tick_least_ms(config, prefill, chip)[1] == "memory"
    # the kernels' least time adds up over the traced ticks of each kind
    one = olmoe_ticks.moe_kernels_least_s(config, program, run, chip, {"decode": 1})
    both = olmoe_ticks.moe_kernels_least_s(config, program, run, chip, {"decode": 3, "prefill": 2})
    assert 0.0075 < one < 0.0080 and both > 5 * one


def test_opcounts_against_numbers_worked_by_hand():
    """Published sizes: 64 experts x 3 matrices x 2048 x 1024."""
    config = published()
    assert config["num_hidden_layers"] == 8
    assert opcounts_olmoe.expert_params_per_layer(config) == 402_653_184
    # every expert of 8 layers in bf16: what a decode tick of 32 slots streams
    assert 8 * 402_653_184 * 2 == 6_442_450_944
    assert opcounts_olmoe.expert_bytes(config, 10 ** 6) == pytest.approx(6_442_450_944)
    # 32 tokens x 8 of 64: 64 (1 - (7/8)^32) = 63.1 experts touched
    assert opcounts_olmoe.experts_touched(config, 32) == pytest.approx(63.108, abs=1e-3)
    assert opcounts_olmoe.experts_touched(config, 1) == pytest.approx(8.0)
    # attention: four 2048 x 2048 projections, two QK-norms, two block norms
    assert opcounts_olmoe.attention_params_per_layer(config) == 4 * 2048 * 2048 + 2 * 2048 + 2 * 2048
    assert opcounts_olmoe.head_params(config) == 2048 * 50304 + 2048
    # an int8 cache position: 2 x 16 heads x (128 codes + a bf16 scale) = 4,160 B a layer
    assert opcounts_olmoe.kv_bytes_per_position(config) == 4160
    assert opcounts_olmoe.kv_bytes_per_position(config, int8=False) == 8192
    # routed FLOPs of a full prefill tick: 2,048 tokens x 8 layers x 8 experts x 12.58 MFLOP
    assert opcounts_olmoe.expert_flops(config, 2048) == 2048 * 8 * 8 * 3 * 2 * 2048 * 1024
    least, bound = opcounts_olmoe.roofline_ms(197e12 * 0.01, 819e9 * 0.02, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(20.0)
