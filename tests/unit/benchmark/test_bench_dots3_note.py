"""The dots3-note-prev cell: at the test preset through ``harness.run_cell``
on the CPU (untraced and traced, the last line held to the contract), its
controls in the program's place, its readers on made-up counters, and its
operation counts against numbers worked by hand for the published sizes.
Nothing here is a measurement."""

import copy
import importlib.util
import json
import os

import numpy as np
import pytest

from benchmarks.lib import dots3_note_ticks, harness, opcounts_dots3_note, peaks, program_spans

CELL, LIKE = "t-longctx", "serve-dots3-note-prev-longctx-sat"
SEED = 2 ** 31 + 37
DEVICE_ONLY = {"decode_roofline_longctx", "prefill_roofline_longctx",
               "dsa_prefill_walk_roofline_longctx",
               "moe_kernel_time_pct_sat", "moe_kernel_roofline_longctx",
               "device_idle_pct_sat", "dsa_attn_time_pct_longctx",
               "dsa_index_decode_roofline_longctx", "dsa_index_prefill_roofline_longctx",
               "dsa_decode_roofline_longctx"}


@pytest.fixture(scope="module")
def longctx_copy(bench_copy):
    """The session's copy of the benchmark with the test cell added to a
    manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "dots3-note-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/dots3-note-test.json",
                                "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "dots3-note-test",
                                  "traffic": "test-longctx", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(longctx_copy):
    root, manifest = longctx_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs",
                           "dots3-note-prev.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, longctx_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(longctx_copy[0], longctx_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines, longctx_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(longctx_copy[0], longctx_copy[1], CELL)
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    # a quarter of the experts is held: three copies in four are another chip's
    assert 45 < metrics["moe_elsewhere_pct_sat"]["value"] < 95
    assert 0 < metrics["moe_pad_pct_sat"]["value"] < 100
    assert 0 < metrics["prefill_fill_pct_sat"]["value"] <= 100
    # 24 chosen of up to 116 live: the selection binds in most ticks
    assert 20 < metrics["sparse_selected_pct_longctx"]["value"] < 90
    # 17 of a ring's 32 positions are a query's window at the most
    assert 0 < metrics["window_read_live_pct_longctx"]["value"] <= 100 * 17 / 32 + 1e-6
    assert metrics["index_read_gb_per_tick_longctx"]["value"] > 0
    assert metrics["recompiles_in_window_sat"]["value"] == 0


@pytest.mark.parametrize("control", ["program", "fp8_weights", "last_positions"])
def test_controls_stand_in_the_programs_place(longctx_copy, control):
    """``tools/dots3_note_controls.py`` at the test preset: each control is a
    server built as the cell builds it, held to the plain reference by the
    runner's own comparison (in float32 on eight tokens nothing here is a
    chip's reading)."""
    spec = importlib.util.spec_from_file_location(
        "dots3_note_controls", os.path.join(harness.REPO_ROOT, "tools", "dots3_note_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(longctx_copy[0], longctx_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, control)))
    assert {"worst_logit_gap", "tol", "ok", "selected_pct"} <= set(line)
    assert line["tol"] == cell.config["serve"]["reference_check"]["logit_gap_tol"]
    assert 30 < line["selected_pct"] < 80          # 24 of up to 67: the selection binds
    if control == "program":
        assert line["ok"] is True and line["worst_logit_gap"] < 1e-3
    elif control == "fp8_weights":
        assert line["weights_are_fp8_values"] is True
    else:
        # the wrong 24 positions are another forward pass: on eight tiny float32
        # tokens its gap is small but it is not the program's zero
        assert line["worst_logit_gap"] > 1e-4
        from deepspeed_tpu.models import deepseek_v3
        assert deepseek_v3.kth_largest.__name__ == "kth_largest"      # the change was undone


def test_the_seeded_draw_widens_what_the_configuration_names_and_nothing_else():
    """``draw`` in the configuration file: the token table 256 times and the
    routed down projections a quarter of the package's plain draw, exactly
    (powers of two), every other leaf as drawn."""
    import jax.numpy as jnp
    from benchmarks.families import dots3_note as family
    draw = published()["draw"]
    assert draw == {"embed_tokens": 256, "routed_down_proj": 0.25}
    leaf = jnp.asarray([[0.02, -0.0137], [1.5, 0.25]], jnp.bfloat16)
    bank = {"experts": {"deepspeed_experts": {"down_proj": {"kernel": leaf},
                                              "up_proj": {"kernel": leaf}}},
            "shared_expert": {"down_proj": {"kernel": leaf}}}
    plain = {"embed_tokens": leaf, "lm_head": {"kernel": leaf},
             "layers_1": {"mlp": bank, "self_attn": {"o_proj": {"kernel": leaf}}}}
    got = family.scaled_draw(plain, draw)
    as_f32 = lambda t: np.asarray(t.astype(jnp.float32))  # noqa: E731
    np.testing.assert_array_equal(as_f32(got["embed_tokens"]), as_f32(leaf) * 256)
    mlp = got["layers_1"]["mlp"]
    np.testing.assert_array_equal(
        as_f32(mlp["experts"]["deepspeed_experts"]["down_proj"]["kernel"]), as_f32(leaf) / 4)
    for same in (got["lm_head"]["kernel"], got["layers_1"]["self_attn"]["o_proj"]["kernel"],
                 mlp["experts"]["deepspeed_experts"]["up_proj"]["kernel"],
                 mlp["shared_expert"]["down_proj"]["kernel"]):
        assert same is leaf
    np.testing.assert_array_equal(as_f32(family.scaled_draw(plain, {})["embed_tokens"]),
                                  as_f32(leaf))


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "dots3_note"
    assert cell.entry["config"] == "dots3-note-prev" and cell.entry["traffic"] == "longctx-sat"
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 512}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 8192, "max": 30720}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert (mix["max_total"], mix["block"], mix["preroll_s"], mix["drain_s"],
            mix["trace_seconds"]) == (32768, 16, 20, 0, 4)
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    # membership, never position: a later PR may append
    names = {m["name"] for m in cell.per_layer}
    own = {base + "_longctx" for base in (
        "decode_roofline", "prefill_roofline", "moe_kernel_roofline",
        "sparse_selected_pct", "index_read_gb_per_tick", "window_read_live_pct",
        "dsa_attn_time_pct", "dsa_index_decode_roofline", "dsa_index_prefill_roofline",
        "dsa_prefill_walk_roofline",
        "dsa_decode_roofline")}
    # the readers it shares with the other saturated cells: one entry each
    shared = {"decode_device_wait_ms_p50_sat", "prefill_device_wait_ms_p50", "sched_host_ms_p50_sat",
              "device_idle_pct_sat", "recompiles_in_window_sat", "slot_occupancy_pct",
              "kv_live_pct_sat", "prefill_fill_pct_sat", "tick_ahead_pct_sat", "moe_pad_pct_sat",
              "moe_elsewhere_pct_sat", "moe_kernel_time_pct_sat"}
    # what its start is made of, under ``setup_s``: the six of ``lib/program_setup.py``
    start = {"setup_trace_lower_s", "setup_backend_load_s", "setup_cache_misses",
             "setup_programs_loaded", "setup_engine_init_s", "setup_import_s"}
    assert own | shared | start <= names
    for metric in cell.per_layer:
        path = os.path.join(harness.REPO_ROOT, "benchmarks", "layer_metrics", metric["name"])
        assert os.path.exists(path + ".py") or os.path.exists(path + ".json")
        if metric["name"] in own | shared | start:
            assert metric["moves"] == ("setup_s" if metric["name"] in start
                                       else "serve_total_tok_s")
        if metric["name"] in own:
            assert metric["workloads"] == [LIKE]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_configuration_is_the_catalogs_but_for_what_it_lists():
    """No width differs from the published config; the cut is the depth (and
    the layer types that go with it), the experts held and the vocabulary
    slice, each with its published value beside it."""
    config = published()
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "n_routed_experts",
                                 "vocab_size"]
    assert (config["published"]["num_hidden_layers"], config["published"]["n_routed_experts"],
            config["published"]["vocab_size"]) == (46, 256, 152064)
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"],
            config["experts_held"], config["n_routed_experts_published"]) == (
                5, 32, 19008, [0, 32], 256)
    assert config["layer_types"] == ["full_attention", "full_attention", "sliding_attention",
                                     "sliding_attention", "sliding_attention"]
    widths = dict(hidden_size=5120, intermediate_size=13824, moe_intermediate_size=1536,
                  q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, num_attention_heads=128, num_key_value_heads=128,
                  swa_q_lora_rank=1024, swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
                  swa_qk_rope_head_dim=64, swa_v_head_dim=128, swa_num_attention_heads=64,
                  swa_num_key_value_heads=64, index_head_dim=128, index_n_heads=64,
                  index_topk=2048, sliding_window_size=513, num_experts_per_tok=8,
                  n_shared_experts=1, routed_scaling_factor=1, first_k_dense_replace=1,
                  rope_theta=80000000, swa_rope_theta=50000, max_position_embeddings=524288,
                  rms_norm_eps=1e-05)
    assert {k: config[k] for k in widths} == widths
    assert config["apply_mla_qkv_lora_rescale"] is True and config["rope_scaling"] is None
    assert config["attention_gate_type"] == config["swa_attention_gate_type"] == "headwise"
    assert config["scoring_func"] == "sigmoid" and config["topk_method"] == "noaux_tc"
    serve = config["serve"]
    assert (serve["max_out_tokens"], serve["kv_quant"], serve["page_size"],
            serve["prefix_cache"], serve["dtype"]) == (32768, False, 16, "off", "bfloat16")
    assert serve["slots"] in (16, 32) and serve["prefill_chunk"] in (256, 512)
    assert serve["reference_check"]["prompt_len"] == 6000
    assert serve["reference_check"]["max_new_tokens"] == 128
    for key in ("weights", "rescale", "gate", "window", "indexer", "n_group", "not_built"):
        assert config["assumed"][key]


@pytest.mark.parametrize("name, counters, want", [
    ("sparse_selected_pct_longctx",
     {"dsa_positions_selected_prefill": 300, "dsa_positions_live_prefill": 2000,
      "dsa_positions_selected_decode": 100, "dsa_positions_live_decode": 2000}, 10.0),
    ("sparse_selected_pct_longctx", {"latent_positions_read_decode": 5}, None),     # no indexer
    ("window_read_live_pct_longctx",
     {"swa_ring_positions_read_prefill": 1000, "swa_ring_positions_live_prefill": 700,
      "swa_ring_positions_read_decode": 3000, "swa_ring_positions_live_decode": 1300}, 50.0),
    ("window_read_live_pct_longctx", {"prefill_positions_fed": 5}, None),           # the parent
    ("index_read_gb_per_tick_longctx",
     {"dsa_index_keys_read_decode": 4_000_000, "decode_slots_computed": 2 * 32}, None),
])
def test_the_new_readers_on_made_up_counters(monkeypatch, name, counters, want):
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    cell = harness.Cell(harness.REPO_ROOT, harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"),
                        LIKE)
    got = module.read({"cell": cell})
    if name.startswith("index_read"):
        slots = cell.config["serve"]["slots"]
        assert got == pytest.approx(4_000_000 * 256 / (2 * 32 / slots) / 1e9)
    else:
        assert got is None if want is None else got == pytest.approx(want)


def test_opcounts_against_numbers_worked_by_hand():
    """Published sizes, five layers (two full, three sliding), 32 of 256
    experts, an eighth of the vocabulary: the issue's arithmetic."""
    config = published()
    ops = opcounts_dots3_note
    assert [ops.layers(config, k) for k in "DEFS"] == [1, 4, 2, 3]
    # q_a 5120 x 1024, q_b 1024 x 128 x 192, kv_a 5120 x 576, kv_b 512 x 128 x 256,
    # o 16384 x 5120, the gate 5120 x 128: 134.68 M; the indexer 9.37 M
    assert ops.attention_matrices(config, "F") == (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576
                                                   + 512 * 128 * 256 + 128 * 128 * 5120
                                                   + 5120 * 128) == 134_676_480
    assert ops.indexer_matrices(config) == 1024 * 64 * 128 + 5120 * 128 + 5120 * 64 == 9_371_648
    # sliding: q_b 1024 x 64 x 256, kv_a 5120 x 1088, kv_b 1024 x 64 x 320, o 8192 x 5120
    assert ops.attention_matrices(config, "S") == (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088
                                                   + 1024 * 64 * 320 + 64 * 128 * 5120
                                                   + 5120 * 64) == 90_832_896
    assert ops.dense_params(config) == 3 * 5120 * 13824 + 5120 == 212_341_760
    assert ops.expert_params(config) == 3 * 5120 * 1536 == 23_592_960
    assert ops.moe_shared_params(config) == 256 * 5120 + 256 + 23_592_960 + 5120
    assert ops.params_held(config) == 4_087_154_176                  # the built tree's leaves
    assert (ops.latent_width(config, "F"), ops.latent_width(config, "S")) == (576, 1088)
    # 32 slots x 32,768: 2 x 1,152 B + 2 x 256 B a position, and 3 rings of 2,176 B a place
    assert ops.cache_bytes(config, 32, 32768, 1024) == 32 * (
        2 * (1152 + 256) * 32768 + 3 * 2176 * 1024) == 3_166_699_520
    assert ops.picks_here(config) == 1.0
    assert ops.experts_touched(config, 32) == pytest.approx(32 * (1 - (31 / 32) ** 32))
    # one decode query at 16,000 live: 64 x 128 x 2 a live pair, 128 x 1,088 x 2 a chosen one
    assert ops.index_flops(config, 16000) == 16000 * 64 * 128 * 2
    flops, nbytes = ops.selected_decode_kernel(config, 1, 2048)
    assert flops == 2048 * 128 * (2 * 512 + 64) * 2
    assert nbytes == 2048 * 1152 + 128 * 1088 * 2
    flops, nbytes = ops.index_kernel(config, 1, 16000, 16000)
    assert nbytes == 16000 * 256 + 64 * (128 * 2 + 4) + 16000 * 4
    pairs = ops.tick_pairs(config, 32, 32, 32 * 16000)
    assert pairs == {"live": 32 * 16000.0, "selected": 32 * 2048.0, "window": 32 * 513.0}
    assert ops.selected_positions(config, 32, 32, 32 * 16000) == 32 * 2048
    assert ops.window_positions(config, 32, 32, 32 * 16000) == 32 * 513
    least, bound = ops.roofline_ms(197e12 * 0.01, 819e9 * 0.02, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(20.0)


def test_tick_roofline_on_made_up_counters():
    config = published()
    serve = dict(config["serve"], slots=32, prefill_chunk=256)
    # 100 decode ticks that fed 20 of 32 slots; 125 working ticks, 22 slots busy at 16,000
    program = {"decode_slots_fed": 2000, "decode_slots_computed": 3200,
               "prefill_positions_fed": 25 * 8 * 256, "prefill_positions_computed": 25 * 32 * 256,
               "moe_rows_routed_decode": 100 * 4 * 20, "moe_experts_touched_decode": 100 * 4 * 15,
               "dsa_positions_live_decode": 100 * 2 * 20 * 16000,
               "dsa_positions_selected_decode": 100 * 2 * 20 * 2048}
    run = {"slot_ticks": 125 * 32, "slot_ticks_busy": 125 * 22,
           "kv_positions_live": 125 * 22 * 16000}
    decode = dots3_note_ticks.tick_shape("decode", program, run, serve)
    assert decode["ticks"] == 100 and decode["tokens"] == 20
    assert decode["kv_positions"] == pytest.approx(20 * 16000)
    pairs = dots3_note_ticks.counted_pairs(config, "decode", program, decode)
    assert pairs["live"] == 20 * 16000 and pairs["selected"] == 20 * 2048
    chip = peaks.peaks_for("TPU v5 lite")
    least, bound, flops, nbytes = dots3_note_ticks.tick_least_ms(config, decode, chip, pairs)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9 * 1e3)
    ops = opcounts_dots3_note
    # 15 of 32 held experts a layer streamed; the index keys of every live position and the
    # chosen latents alone; the windows; the new rows
    assert nbytes == (4 * 15 * 23_592_960 * 2
                      + 2 * (2 * (ops.attention_params(config, "F") + ops.indexer_params(config))
                             + 3 * ops.attention_params(config, "S") + ops.dense_params(config)
                             + 4 * ops.moe_shared_params(config) + ops.head_params(config))
                      + 2 * (2 * (320_000 * 128 + 20 * 2048 * 576 + 20 * (576 + 128))
                             + 3 * (20 * 513 + 20) * 1088))
    # three decode ticks' kernels from what those ticks counted (both full layers summed)
    counted = {"ticks": 3, "dsa_positions_live": 3 * 2 * 320_000,
               "dsa_positions_selected": 3 * 2 * 20 * 2048, "latent_positions_live": 3 * 2 * 320_000}
    index = dots3_note_ticks.kernel_least_s(config, counted, chip, "index")
    assert index == pytest.approx(3 * 2 * (320_000 * 256 + 320_000 * 4) / 819e9)
    chosen = dots3_note_ticks.kernel_least_s(config, counted, chip, "decode")
    # 128 heads x 1,088 x 2 a chosen pair against 1,152 B: compute and memory meet here
    assert chosen == pytest.approx(max(3 * 2 * 20 * 2048 * 128 * 1088 * 2 / 197e12,
                                       3 * 2 * 20 * 2048 * 1152 / 819e9))
    # a prefill tick of 8 slots x 256 queries ending at 10,000: the chosen pairs' attention
    # and every live position's keys and values made once
    counted = {"dsa_positions_selected": 2 * 8 * 256 * 2048, "latent_positions_live": 2 * 80_000}
    walk = dots3_note_ticks.kernel_least_s(config, counted, chip, "walk")
    assert walk == pytest.approx(2 * (2 * 8 * 256 * 2048 * 128 * 320
                                      + 2 * 80_000 * 512 * 128 * 256) / 197e12)


def test_traced_counts_take_the_ticks_the_slice_holds_whole(monkeypatch):
    """Made-up ring: five ticks of one second; the slice is the last 2.5 s, so
    the last two ticks are whole in it and the third is cut by its start."""
    from deepspeed_tpu.utils.trace import Record
    records, seq = [], 0
    for i, kind in enumerate(["prefill", "decode", "prefill", "prefill", "decode"]):
        for name, n in (("dsa_positions_live", 100 * (i + 1)), ("latent_positions_live", 10)):
            seq += 1
            records.append(Record(seq, "count:" + name, i + 0.9, i + 0.9, ("tick", "commit"), n,
                                  "sched", kind))
        seq += 1
        records.append(Record(seq, "tick", float(i), i + 1.0, (), i, "sched", kind))
    monkeypatch.setattr(program_spans, "ring", lambda: (records, {}))
    got = dots3_note_ticks.traced_counts(2.5)
    assert got == {"prefill": {"ticks": 1, "dsa_positions_live": 400, "latent_positions_live": 10},
                   "decode": {"ticks": 1, "dsa_positions_live": 500, "latent_positions_live": 10}}
    monkeypatch.setattr(program_spans, "ring", lambda: ([r for r in records if r.name == "tick"], {}))
    assert dots3_note_ticks.traced_counts(2.5) == {}             # the parent: no counts
