"""The JoyAI-LLM-Flash cell: at the test preset through ``harness.run_cell``
on the CPU (untraced and traced, the last line held to the contract), its
readers on made-up counters, and its operation counts against numbers worked
by hand for the published sizes and for one tiny tick in both forms of the
attention. Nothing here is a measurement."""

import copy
import json
import os

import pytest

from benchmarks.lib import harness, joyai_llm_flash_ticks, opcounts_joyai_llm_flash, peaks
from benchmarks.lib import program_spans

CELL, LIKE = "t-longdoc", "serve-joyai-llm-flash-longdoc-sat"
SEED = 2 ** 31 + 32
DEVICE_ONLY = {"decode_roofline_longdoc", "prefill_roofline_longdoc",
               "moe_kernel_time_pct_sat", "moe_kernel_roofline_longdoc",
               "device_idle_pct_sat", "mla_attn_time_pct_longdoc",
               "mla_decode_roofline_longdoc"}


@pytest.fixture(scope="module")
def longdoc_copy(bench_copy):
    """The session's copy of the benchmark with the test cell added to a
    manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "joyai-llm-flash-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/joyai-llm-flash-test.json",
                                "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "joyai-llm-flash-test",
                                  "traffic": "test-longdoc", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(longdoc_copy):
    root, manifest = longdoc_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, longdoc_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(longdoc_copy[0], longdoc_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines, longdoc_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(longdoc_copy[0], longdoc_copy[1], CELL)
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    # a quarter of the experts is held: three copies in four are another chip's
    # (this seed's sixteen-expert router reads 58-66 as the window's ticks fall)
    assert 45 < metrics["moe_elsewhere_pct_sat"]["value"] < 90
    assert 0 < metrics["moe_pad_pct_sat"]["value"] < 100
    assert 0 < metrics["prefill_fill_pct_sat"]["value"] <= 100
    # a decode tick reads whole pools, a prefill tick whole key blocks
    assert 0 < metrics["latent_read_live_pct_longdoc"]["value"] < 100
    assert metrics["recompiles_in_window_sat"]["value"] == 0


@pytest.mark.parametrize("control", ["program", "fp8_weights"])
def test_controls_stand_in_the_programs_place(longdoc_copy, control):
    """``tools/joyai_llm_flash_controls.py`` at the test preset: each control
    is a server built as the cell builds it, held to the plain reference by
    the runner's own comparison (in float32 on eight tokens nothing here is
    a chip's reading)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "joyai_llm_flash_controls",
        os.path.join(harness.REPO_ROOT, "tools", "joyai_llm_flash_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(longdoc_copy[0], longdoc_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, control)))
    assert {"worst_logit_gap", "tol", "ok", "experts_touched_a_decode_tick_a_layer"} <= set(line)
    assert line["tol"] == cell.config["serve"]["reference_check"]["logit_gap_tol"]
    assert 0 < line["experts_touched_a_decode_tick_a_layer"] <= 4
    if control == "program":
        assert line["ok"] is True and line["worst_logit_gap"] < 1e-3
    else:
        # on eight tiny float32 tokens the rounded server still picks the
        # reference's tokens: only that the rounding was made is held here
        assert line["weights_are_fp8_values"] is True


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "joyai_llm_flash"
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 512}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 4096, "max": 15360}
    assert mix["output_len"] == {"dist": "uniform", "min": 64, "max": 512}
    assert (mix["max_total"], mix["block"], mix["preroll_s"], mix["drain_s"],
            mix["trace_seconds"]) == (16384, 16, 20, 0, 4)
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    # the family's own; the rest are readers the cell shares, one entry each
    own = [name for name in names if name.endswith("_longdoc")]
    assert len(own) == 6
    for metric in cell.per_layer:
        path = os.path.join(harness.REPO_ROOT, "benchmarks", "layer_metrics", metric["name"])
        assert os.path.exists(path + ".py") or os.path.exists(path + ".json")
        assert metric["moves"] in ("serve_total_tok_s", "setup_s")
        if metric["name"] in own:
            assert metric["moves"] == "serve_total_tok_s" and metric["workloads"] == [LIKE]


def test_the_configuration_is_the_catalogs_but_for_what_it_lists():
    """No width differs from the published config; the cut is the depth, the
    experts held and the vocabulary slice, each with its published value
    beside it."""
    config = published()
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"],
            config["experts_held"], config["n_routed_experts_published"]) == (
                10, 64, 32320, [0, 64], 256)
    widths = dict(hidden_size=2048, intermediate_size=7168, moe_intermediate_size=768,
                  q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  qk_head_dim=192, v_head_dim=128, head_dim=64, num_attention_heads=32,
                  num_key_value_heads=32, num_experts_per_tok=8, n_shared_experts=1,
                  routed_scaling_factor=2.5, first_k_dense_replace=1, rope_theta=32000000,
                  max_position_embeddings=131072, num_nextn_predict_layers=1, n_group=1,
                  topk_group=1)
    assert {k: config[k] for k in widths} == widths
    assert config["rope_interleave"] is True and config["rope_scaling"] is None
    assert config["scoring_func"] == "sigmoid" and config["topk_method"] == "noaux_tc"
    serve = config["serve"]
    assert (serve["slots"], serve["max_out_tokens"], serve["kv_quant"], serve["page_size"],
            serve["prefix_cache"]) == (32, 16384, False, 16, "off")
    assert serve["reference_check"]["prompt_len"] == 6000
    assert serve["reference_check"]["max_new_tokens"] == 128


@pytest.mark.parametrize("counters, want", [
    ({"latent_positions_read_prefill": 3000, "latent_positions_live_prefill": 2500,
      "latent_positions_read_decode": 7000, "latent_positions_live_decode": 1500}, 40.0),
    ({"latent_positions_read_decode": 500, "latent_positions_live_decode": 500}, 100.0),
    ({"prefill_positions_fed": 5}, None),                # the parent: no such counter
])
def test_latent_read_live_pct_on_made_up_counters(monkeypatch, counters, want):
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics",
                                 "latent_read_live_pct_longdoc.py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    got = module.read({})
    assert got is None if want is None else got == pytest.approx(want)


def test_tick_roofline_on_made_up_counters():
    config = published()
    serve = config["serve"]
    # 100 decode ticks that fed 20 of 32 slots, 25 prefill ticks that fed 3 slots;
    # 125 working ticks with 22 slots busy holding 9,000 positions each
    program = {"decode_slots_fed": 2000, "decode_slots_computed": 3200,
               "prefill_positions_fed": 25 * 1536, "prefill_positions_computed": 25 * 32 * 512,
               "moe_rows_routed_decode": 100 * 9 * 40, "moe_experts_touched_decode": 100 * 9 * 28}
    run = {"slot_ticks": 125 * 32, "slot_ticks_busy": 125 * 22, "kv_positions_live": 125 * 22 * 9000}
    decode = joyai_llm_flash_ticks.tick_shape("decode", program, run, serve)
    assert decode["ticks"] == 100 and decode["tokens"] == 20 and decode["sequences"] == 20
    assert decode["kv_positions"] == pytest.approx(20 * 9000)
    assert decode["rows"] == 360 and decode["touched"] == 252
    chip = peaks.peaks_for("TPU v5 lite")
    least, bound, flops, nbytes = joyai_llm_flash_ticks.tick_least_ms(config, decode, chip)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9 * 1e3)
    ops = opcounts_joyai_llm_flash
    # 28 of 64 held experts a layer streamed, the live latent once a layer
    assert nbytes == (9 * 28 * 4_718_592 * 2 + 2 * (10 * 26_349_568 + 44_042_240
                                                     + 9 * 5_245_184 + 66_193_408)
                      + 10 * (180_000 + 20) * 1152)
    assert 5.5 < least < 6.5
    prefill = joyai_llm_flash_ticks.tick_shape("prefill", program, run, serve)
    assert prefill["tokens"] == 1536 and prefill["sequences"] == 3
    assert joyai_llm_flash_ticks.tick_least_ms(config, prefill, chip)[1] == "compute"
    one = joyai_llm_flash_ticks.moe_kernels_least_s(config, program, run, chip, {"decode": 1})
    assert one == pytest.approx((9 * 28 * 4_718_592 * 2 + 360 * (2 * 2048 + 3 * 768) * 2) / 819e9)
    # three decode ticks' kernels: ten layers each reading 180,000 live positions of 1,152 B
    # (and 20 queries x 32 heads x 1,088 values in and out)
    assert joyai_llm_flash_ticks.decode_kernels_least_s(config, program, run, chip, 3) == (
        pytest.approx(3 * 10 * (180_000 * 1152 + 20 * 32 * 1088 * 2) / 819e9))


def test_opcounts_against_numbers_worked_by_hand():
    """Published sizes, ten layers, 64 of 256 experts, a quarter of the vocabulary."""
    config = published()
    ops = opcounts_joyai_llm_flash
    assert [ops.layers(config, k) for k in "DEA"] == [1, 9, 10]
    # q_a 2048 x 1536 and its norm, q_b 1536 x 32 x 192, kv_a 2048 x 576 and its
    # norm, kv_b 512 x 32 x 256, o 4096 x 2048, the block's first norm
    assert ops.attention_params(config) == (2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512
                                            + 512 * 8192 + 4096 * 2048 + 2048) == 26_349_568
    assert ops.dense_params(config) == 3 * 2048 * 7168 + 2048 == 44_042_240
    assert ops.expert_params(config) == 3 * 2048 * 768 == 4_718_592
    assert ops.moe_shared_params(config) == 256 * 2048 + 256 + 4_718_592 + 2048 == 5_245_184
    assert ops.head_params(config) == 2048 * 32320 + 2048
    assert ops.params_held(config) == pytest.approx(3.205e9, rel=1e-3)       # 6.41 GB of bf16
    assert ops.latent_width(config) == 576
    assert ops.picks_here(config) == 2.0
    assert ops.experts_touched(config, 1) == pytest.approx(2.0)
    assert ops.experts_touched(config, 24) == pytest.approx(64 * (1 - (31 / 32) ** 24))
    assert ops.expert_flops(config, 512) == 9 * 512 * 2.0 * 2 * 4_718_592
    least, bound = ops.roofline_ms(197e12 * 0.01, 819e9 * 0.02, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(20.0)


def test_one_tiny_tick_in_both_forms_of_the_attention_by_hand():
    """Two heads of nope 4, rope 2, value 3 over a latent of rank 5; one
    layer's attention proper. Expanded: a pair costs 2 x heads x (4 + 2 + 3),
    a cached position's keys and values 2 x rank x heads x (4 + 3). Absorbed:
    a pair costs 2 x heads x (5 + 2 + 5), a query's two absorbing projections
    2 x heads x rank x (4 + 3)."""
    config = dict(num_attention_heads=2, qk_nope_head_dim=4, qk_rope_head_dim=2, v_head_dim=3,
                  kv_lora_rank=5, num_hidden_layers=1, first_k_dense_replace=1)
    ops = opcounts_joyai_llm_flash
    # a decode tick: 3 queries, each over 10 positions
    assert ops.attention_flops(config, 3, 30) == 2 * 30 * 2 * 12 + 2 * 3 * 2 * 5 * 7 == 1860
    assert ops.attention_flops(config, 3, 30, expanded_positions=30) == (
        2 * 30 * 2 * 9 + 2 * 30 * 5 * 2 * 7) == 5280
    assert ops.tick_attention_flops(config, 3, 3, 30) == 1860            # absorbed is cheaper
    # a prefill chunk: 1 sequence, 8 queries ending at length 10: 6.5 positions a query
    assert ops.tick_attention_flops(config, 8, 1, 10) == min(
        2 * 52 * 2 * 12 + 2 * 8 * 2 * 5 * 7, 2 * 52 * 2 * 9 + 2 * 10 * 5 * 2 * 7) == 3272
    # the latent pool: the live positions read once and the new rows written, bf16
    assert ops.latent_bytes(config, 8, 10) == (10 + 8) * 7 * 2
    # the decode kernel alone: scores and the weighted sum, no absorbing projection
    assert ops.decode_kernel_flops(config, 30) == 2 * 30 * 2 * 12
    assert ops.decode_kernel_bytes(config, 3, 30) == 30 * 7 * 2 + 3 * 2 * 12 * 2
