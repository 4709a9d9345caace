"""The DeepSeek-V3.2 cell: at the test preset through ``harness.run_cell`` on
the CPU (untraced and traced, the last line held to the contract), its controls
in the program's place, its readers on made-up counters, the file's parameter
counts against the built tree's leaves, and its operation counts against numbers
worked by hand for the published sizes. Nothing here is a measurement."""

import copy
import importlib.util
import json
import os

import pytest

from benchmarks.lib import deepseek_v32_ticks, harness, opcounts_deepseek_v32, peaks, program_spans

CELL, LIKE = "t-ctx32k", "serve-deepseek-v3.2-ctx32k-sat"
SEED = 2 ** 31 + 58
OWN = {base + "_ctx32k" for base in (
    "decode_roofline", "prefill_roofline", "moe_kernel_roofline", "dsa_index_decode_roofline",
    "dsa_index_prefill_roofline", "dsa_decode_roofline", "dsa_prefill_walk_roofline",
    "dsa_attn_time_pct", "sparse_selected_pct", "index_read_gb_per_tick", "moe_group_kept_pct")}
DEVICE_ONLY = {"decode_roofline_ctx32k", "prefill_roofline_ctx32k",
               "dsa_prefill_walk_roofline_ctx32k", "moe_kernel_time_pct_sat",
               "moe_kernel_roofline_ctx32k", "device_idle_pct_sat", "dsa_attn_time_pct_ctx32k",
               "dsa_index_decode_roofline_ctx32k", "dsa_index_prefill_roofline_ctx32k",
               "dsa_decode_roofline_ctx32k"}


@pytest.fixture(scope="module")
def ctx32k_copy(bench_copy):
    """The session's copy of the benchmark with the test cell added to a
    manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "deepseek-v3.2-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/deepseek-v3.2-test.json",
                                "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "deepseek-v3.2-test",
                                  "traffic": "test-ctx32k", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(ctx32k_copy):
    root, manifest = ctx32k_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs", "deepseek-v3.2.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, ctx32k_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(ctx32k_copy[0], ctx32k_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines, ctx32k_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(ctx32k_copy[0], ctx32k_copy[1], CELL)
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    # an eighth of the experts is held: seven copies in eight are another chip's
    assert 60 < metrics["moe_elsewhere_pct_sat"]["value"] < 99
    assert 0 < metrics["prefill_fill_pct_sat"]["value"] <= 100
    # 24 chosen of up to 116 live, in all four layers: the selection binds in most ticks
    assert 20 < metrics["sparse_selected_pct_ctx32k"]["value"] < 90
    assert metrics["index_read_gb_per_tick_ctx32k"]["value"] > 0
    # two of four groups kept: about half the rows may reach the held experts' group
    assert 20 < metrics["moe_group_kept_pct_ctx32k"]["value"] < 80
    assert metrics["recompiles_in_window_sat"]["value"] == 0


@pytest.mark.parametrize("control", ["program", "fp8_weights", "last_positions", "flat_top_k",
                                     "plain_rope"])
def test_controls_stand_in_the_programs_place(ctx32k_copy, control):
    """``tools/dots3_note_controls.py`` at the test preset: each control is a
    server built as the cell builds it, held to the plain reference by the
    runner's own comparison (in float32 on eight tokens nothing here is a
    chip's reading)."""
    spec = importlib.util.spec_from_file_location(
        "dots3_note_controls", os.path.join(harness.REPO_ROOT, "tools", "dots3_note_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(ctx32k_copy[0], ctx32k_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, control)))
    assert {"worst_logit_gap", "tol", "ok", "selected_pct"} <= set(line)
    assert line["tol"] == cell.config["serve"]["reference_check"]["logit_gap_tol"]
    assert 30 < line["selected_pct"] < 80          # 24 of up to 67: the selection binds
    if control == "program":
        assert line["ok"] is True and line["worst_logit_gap"] < 1e-3
    elif control == "fp8_weights":
        assert line["weights_are_fp8_values"] is True
    else:
        # another forward pass: on eight tiny float32 tokens its gap is small
        # but it is not the program's zero
        assert line["worst_logit_gap"] > 1e-4


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "deepseek_v32"
    assert cell.entry["config"] == "deepseek-v3.2" and cell.entry["traffic"] == "ctx32k-sat"
    assert len(cell.entry["why"]) <= 200
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 256}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 23552, "max": 25600}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert (mix["max_total"], mix["block"], mix["preroll_s"], mix["drain_s"],
            mix["trace_seconds"]) == (32768, 16, 20, 0, 6)
    assert "rate_rps" not in mix["arrivals"]
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}        # membership, never position
    shared = {"decode_device_wait_ms_p50_sat", "prefill_device_wait_ms_p50",
              "sched_host_ms_p50_sat", "device_idle_pct_sat", "recompiles_in_window_sat",
              "slot_occupancy_pct", "kv_live_pct_sat", "prefill_fill_pct_sat",
              "tick_ahead_pct_sat", "moe_pad_pct_sat", "moe_elsewhere_pct_sat",
              "moe_kernel_time_pct_sat", "program_operand_leaves_sat", "backlog_left_pct_sat"}
    start = {"setup_trace_lower_s", "setup_backend_load_s", "setup_cache_misses",
             "setup_programs_loaded", "setup_engine_init_s", "setup_import_s"}
    assert OWN | shared | start <= names
    for metric in cell.per_layer:
        path = os.path.join(harness.REPO_ROOT, "benchmarks", "layer_metrics", metric["name"])
        assert os.path.exists(path + ".py") or os.path.exists(path + ".json")
        if metric["name"] in OWN:
            assert metric["workloads"] == [LIKE] and metric["moves"] == "serve_total_tok_s"
    # the new entries stand at the end of their lists
    assert manifest["configs"][-1]["name"] == "deepseek-v3.2"
    assert manifest["workloads"][-1]["name"] == LIKE
    assert {m["name"] for m in manifest["per_layer"][-len(OWN):]} == OWN
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_catalogs_but_for_what_it_lists():
    """Every number of the catalog's ``config`` stands in the file but the four
    the file lists as reduced, each with its published value beside it."""
    config = published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2")
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
        assert differs == sorted(config["reduced"])
        assert {k: row["config"][k] for k in config["reduced"]} == config["published"]
    assert config["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                   "n_routed_experts": 256, "vocab_size": 129280}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"], config["experts_held"],
            config["n_routed_experts_published"]) == (6, 1, 8, 16160, [0, 8], 256)
    widths = dict(hidden_size=7168, intermediate_size=18432, moe_intermediate_size=2048,
                  q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, num_attention_heads=128, index_head_dim=128, index_n_heads=64,
                  index_topk=2048, num_experts_per_tok=8, n_shared_experts=1, n_group=8,
                  topk_group=4, routed_scaling_factor=2.5, rope_theta=10000,
                  max_position_embeddings=163840, rms_norm_eps=1e-06,
                  num_nextn_predict_layers=1)
    assert {k: config[k] for k in widths} == widths
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    serve = config["serve"]
    assert (serve["slots"], serve["max_out_tokens"], serve["kv_quant"], serve["page_size"],
            serve["prefix_cache"], serve["dtype"], serve["prefill_interleave"]) == (
                16, 32768, False, 16, "off", "bfloat16", 16)
    assert serve["prefill_chunk"] in (128, 256)
    assert serve["reference_check"]["prompt_len"] == 6000
    assert serve["reference_check"]["max_new_tokens"] == 128
    for key in ("weights", "indexer", "router", "rope", "not_built"):
        assert config["assumed"][key]
    assert "thirty-two" in config["deployment"]


def test_the_files_parameter_counts_are_the_built_trees_leaves():
    """``opcounts_deepseek_v32.params_held`` at the configuration's sizes, the
    number ``reduced_why`` quotes, is the size of the tree the family builds
    (shapes only: nothing is allocated), and the cache is the reckoned bytes."""
    import jax
    import jax.numpy as jnp
    from benchmarks.families import deepseek_v32 as family
    from deepspeed_tpu.inference.serving.programs import make_slot_cache

    config = published()
    module = family.model(config, config["serve"])
    shapes = jax.eval_shape(lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    built = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    ops = opcounts_deepseek_v32
    assert built == ops.params_held(config) == 3_825_510_144
    assert f"{built:,}" in config["reduced_why"]
    cache = jax.eval_shape(lambda: make_slot_cache(module, config["serve"]["slots"]))
    pools = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache)
                if leaf.ndim == 4)
    assert pools == ops.cache_bytes(config, 16, 32768) == 16 * 32768 * 6 * 1408 == 4_429_185_024


@pytest.mark.parametrize("name, counters, want", [
    ("sparse_selected_pct_ctx32k",
     {"dsa_positions_selected_prefill": 300, "dsa_positions_live_prefill": 2000,
      "dsa_positions_selected_decode": 100, "dsa_positions_live_decode": 2000}, 10.0),
    ("sparse_selected_pct_ctx32k", {"latent_positions_read_decode": 5}, None),      # no indexer
    ("moe_group_kept_pct_ctx32k",
     {"moe_rows_group_kept_prefill": 900, "moe_rows_group_routed_prefill": 2000,
      "moe_rows_group_kept_decode": 100, "moe_rows_group_routed_decode": 500}, 40.0),
    ("moe_group_kept_pct_ctx32k", {"moe_rows_routed_prefill": 5}, None),             # the parent
    ("index_read_gb_per_tick_ctx32k",
     {"dsa_index_keys_read_decode": 4_000_000, "decode_slots_computed": 2 * 16},
     4_000_000 * 256 / 2 / 1e9),
    ("index_read_gb_per_tick_ctx32k", {"decode_slots_computed": 32}, None),
])
def test_the_new_readers_on_made_up_counters(monkeypatch, name, counters, want):
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    cell = harness.Cell(harness.REPO_ROOT, harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"),
                        LIKE)
    got = module.read({"cell": cell})
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_reader_finds_nothing_on_a_program_that_lacks_what_it_reads(monkeypatch, name):
    """Over the parent's program (no counters of this PR, no trace): None, no raise."""
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], {}))
    cell = harness.Cell(harness.REPO_ROOT, harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"),
                        LIKE)
    ctx = {"cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"), "counters": {}, "spans": {},
           "trace": {"window_s": 1.0, "family_seconds": {}, "busy_s": 0.0, "busy_s_first": 0.0,
                     "idle_gaps": {}}}
    assert module.read(ctx) is None


def test_opcounts_against_numbers_worked_by_hand():
    """Published sizes, six layers (one dense), 8 of 256 experts, an eighth of
    the vocabulary: the issue's arithmetic."""
    config = published()
    ops = opcounts_deepseek_v32
    assert [ops.layers(config, k) for k in "DEF"] == [1, 5, 6]
    # q_a 7168 x 1536, q_b 1536 x 128 x 192, kv_a 7168 x 576, kv_b 512 x 128 x 256,
    # o 16384 x 7168: 187.11 M; the indexer 1536 x 64 x 128 + 7168 x (128 + 64): 13.96 M
    assert ops.attention_matrices(config) == (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                                              + 512 * 128 * 256 + 128 * 128 * 7168) == 187_105_280
    assert ops.indexer_matrices(config) == 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 == 13_959_168
    assert ops.dense_params(config) == 3 * 7168 * 18432 + 7168 == 396_368_896
    assert ops.expert_params(config) == 3 * 7168 * 2048 == 44_040_192
    assert ops.moe_shared_params(config) == 256 * 7168 + 256 + 44_040_192 + 7168
    assert ops.head_params(config) == 7168 * 16160 + 7168
    assert ops.params_held(config) == 3_825_510_144                  # the built tree's leaves
    assert ops.latent_width(config) == 576
    assert ops.cache_bytes(config, 16, 32768) == 16 * 6 * (1152 + 256) * 32768
    assert ops.picks_here(config) == 0.25 and ops.group_kept_share(config) == 0.5
    assert ops.experts_touched(config, 16) == pytest.approx(8 * (1 - (31 / 32) ** 16))
    # one decode query at 25,000 live: 64 x 128 x 2 a live pair, 128 x 1,088 x 2 a chosen one
    assert ops.index_flops(config, 25000) == 25000 * 64 * 128 * 2
    flops, nbytes = ops.selected_decode_kernel(config, 1, 2048)
    assert flops == 2048 * 128 * (2 * 512 + 64) * 2
    assert nbytes == 2048 * 1152 + 128 * 1088 * 2
    flops, nbytes = ops.index_kernel(config, 1, 25000, 25000)
    assert nbytes == 25000 * 256 + 64 * (128 * 2 + 4) + 25000 * 4
    pairs = ops.tick_pairs(config, 16, 16, 16 * 25000)
    assert pairs == {"live": 16 * 25000.0, "selected": 16 * 2048.0}
    # a decode tick of 16 slots at 25,000: per token every layer's matrices, the
    # dense layer, five routers and shared experts; the pairs; a quarter-row a
    # token a layer routed here; the head's slice
    flops = ops.tick_flops(config, 16, 16, 16 * 25000)
    assert flops == (16 * 2 * (6 * (187_105_280 + 13_959_168) + 3 * 7168 * 18432
                               + 5 * (256 * 7168 + 44_040_192))
                     + 6 * (16 * 25000 * 64 * 128 * 2
                            + 2 * 16 * 2048 * 128 * 1088 + 2 * 16 * 128 * 512 * 256)
                     + 5 * 16 * 0.25 * 2 * 44_040_192 + 16 * 2 * 7168 * 16160)
    nbytes = ops.tick_bytes(config, 16, 16, 16 * 25000, touched=3)
    assert nbytes == (5 * 3 * 44_040_192 * 2
                      + 2 * (6 * (ops.attention_params(config) + ops.indexer_params(config))
                             + ops.dense_params(config) + 5 * ops.moe_shared_params(config)
                             + ops.head_params(config))
                      + 2 * 6 * (16 * 25000 * 128 + 16 * 2048 * 576 + 16 * (576 + 128)))
    least, bound = ops.roofline_ms(flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9 * 1e3)


def test_tick_roofline_on_made_up_counters():
    config = published()
    serve = dict(config["serve"])
    # 100 decode ticks that fed 10 of 16 slots; 125 working ticks, 12 slots busy at 20,000
    program = {"decode_slots_fed": 1000, "decode_slots_computed": 1600,
               "prefill_positions_fed": 25 * 4 * 256, "prefill_positions_computed": 25 * 16 * 256,
               "moe_rows_routed_decode": 100 * 5 * 3, "moe_experts_touched_decode": 100 * 5 * 2,
               "dsa_positions_live_decode": 100 * 6 * 10 * 20000,
               "dsa_positions_selected_decode": 100 * 6 * 10 * 2048}
    run = {"slot_ticks": 125 * 16, "slot_ticks_busy": 125 * 12,
           "kv_positions_live": 125 * 12 * 20000}
    decode = deepseek_v32_ticks.tick_shape("decode", program, run, serve)
    assert decode["ticks"] == 100 and decode["tokens"] == 10
    pairs = deepseek_v32_ticks.counted_pairs(config, "decode", program, decode)
    assert pairs["live"] == 10 * 20000 and pairs["selected"] == 10 * 2048
    chip = peaks.peaks_for("TPU v5 lite")
    least, bound, flops, nbytes = deepseek_v32_ticks.tick_least_ms(config, decode, chip, pairs)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9 * 1e3)
    ops = opcounts_deepseek_v32
    assert nbytes == ops.tick_bytes(config, 10, 10, decode["kv_positions"], touched=2)
    # three decode ticks' kernels from what those ticks counted (six layers summed)
    counted = {"ticks": 3, "dsa_positions_live": 3 * 6 * 200_000,
               "dsa_positions_selected": 3 * 6 * 10 * 2048, "latent_positions_live": 3 * 6 * 200_000}
    index = deepseek_v32_ticks.kernel_least_s(config, counted, chip, "index")
    assert index == pytest.approx(3 * 6 * (200_000 * 256 + 200_000 * 4) / 819e9)
    chosen = deepseek_v32_ticks.kernel_least_s(config, counted, chip, "decode")
    assert chosen == pytest.approx(max(3 * 6 * 10 * 2048 * 128 * 1088 * 2 / 197e12,
                                       3 * 6 * 10 * 2048 * 1152 / 819e9))
    counted = {"dsa_positions_selected": 6 * 4 * 256 * 2048, "latent_positions_live": 6 * 80_000}
    walk = deepseek_v32_ticks.kernel_least_s(config, counted, chip, "walk")
    assert walk == pytest.approx(6 * (2 * 4 * 256 * 2048 * 128 * 320
                                      + 2 * 80_000 * 512 * 128 * 256) / 197e12)
    # the matmuls of 2 touched experts a layer over a traced decode tick
    least_s = deepseek_v32_ticks.moe_kernels_least_s(config, program, run, chip, {"decode": 4})
    assert least_s == pytest.approx(4 * (5 * 2 * 44_040_192 * 2
                                         + 15 * (2 * 7168 + 3 * 2048) * 2) / 819e9)
