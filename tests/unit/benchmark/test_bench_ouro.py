"""The Ouro cell: at the test preset through ``harness.run_cell`` on the CPU
(untraced and traced, the last line held to the contract), the two loop
controls in the program's place, its readers on made-up counters, and its
operation counts against the built tree, the slot cache and numbers worked by
hand for the published sizes. Nothing here is a measurement."""

import copy
import importlib.util
import json
import os

import pytest

from benchmarks.lib import harness, opcounts_ouro, ouro_ticks, peaks, program_spans

CELL, LIKE = "t-mathword", "serve-ouro-2.6b-mathword-sat"
SEED = 2 ** 31 + 50
NEW = {"decode_roofline_looped", "prefill_roofline_looped", "kv_read_live_pct_looped",
       "loop_kv_read_gb_per_tick_looped", "pool_decode_time_pct_looped",
       "pool_decode_roofline_looped"}
JOINED = {"recompiles_in_window_sat", "slot_occupancy_pct", "kv_live_pct_sat",
          "device_idle_pct_sat", "sched_host_ms_p50_sat", "prefill_device_wait_ms_p50",
          "decode_device_wait_ms_p50_sat", "prefill_fill_pct_sat", "prefill_rung_fill_pct_sat",
          "tick_ahead_pct_sat"}
DEVICE_ONLY = {"decode_roofline_looped", "prefill_roofline_looped", "pool_decode_time_pct_looped",
               "pool_decode_roofline_looped", "device_idle_pct_sat"}


@pytest.fixture(scope="module")
def mathword_copy(bench_copy):
    """The session's copy of the benchmark with the test cell added to a
    manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "ouro-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/ouro-test.json", "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "ouro-test",
                                  "traffic": "test-mathword", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(mathword_copy):
    root, manifest = mathword_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, mathword_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(mathword_copy[0], mathword_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_every_new_and_every_joined_metric(lines, mathword_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(mathword_copy[0], mathword_copy[1], CELL)
    names = {m["name"] for m in cell.per_layer}
    assert NEW | JOINED <= names
    assert not [n for n in names if n.endswith(("_agent", "_reason", "_longdoc", "_longctx",
                                                "_mixedlen")) or n.startswith("moe_")]
    assert set(metrics) == names - DEVICE_ONLY
    # off the chip a decode tick walks every slot's pool together as far as the longest goes
    assert 5 < metrics["kv_read_live_pct_looped"]["value"] <= 100
    # 4 slots x at most 64 positions x 9 walks x 2 x 4 heads x (16 + 4: float32 scales here)
    assert 0 < metrics["loop_kv_read_gb_per_tick_looped"]["value"] <= 4 * 64 * 9 * 160 / 1e9
    assert metrics["recompiles_in_window_sat"]["value"] == 0


@pytest.mark.parametrize("control", ["three_passes", "shared_pass_cache"])
def test_a_loop_control_stands_in_the_programs_place(mathword_copy, control):
    """``tools/dots3_note_controls.py`` at the test preset: a server built as the
    cell builds it that runs a pass fewer, or whose passes share pass 1's pool,
    held to the plain reference by the runner's own comparison: not correct (at
    float32 over 8 tokens; the chip's readings are in the configuration's file)."""
    spec = importlib.util.spec_from_file_location(
        "dots3_note_controls", os.path.join(harness.REPO_ROOT, "tools", "dots3_note_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(mathword_copy[0], mathword_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, control)))
    assert line["tol"] == cell.config["serve"]["reference_check"]["logit_gap_tol"]
    assert line["ok"] is False and line["worst_logit_gap"] > line["tol"]
    from deepspeed_tpu.models import llama
    assert llama._cache_of_pass.__name__ == "_cache_of_pass"       # the package is as it was


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "ouro"
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 960}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 96, "sigma": 0.5, "min": 48,
                                 "max": 192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 192, "sigma": 0.5, "min": 96,
                                 "max": 320}
    assert (mix["max_total"], mix["preroll_s"], mix["drain_s"], mix["trace_seconds"]) == (512, 20, 0, 4)
    assert mix["block"] in (16, 8)       # 8: the issue's one remedy for the seeds' spread
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    assert NEW | JOINED <= {m["name"] for m in cell.per_layer}
    config = cell.config
    # nothing is cut: every published key at its value
    assert config["reduced"] == [] and config["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert (config["num_hidden_layers"], config["total_ut_steps"], config["early_exit_threshold"],
            config["vocab_size"], config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["max_position_embeddings"], config["rope_theta"], config["rms_norm_eps"]) == (
                48, 4, 1, 49152, 2048, 5632, 16, 16, 128, 65536, 1000000, 1e-6)
    assert config["layer_types"] == ["full_attention"] * 48
    serve = config["serve"]
    assert (serve["slots"], serve["max_out_tokens"], serve["kv_quant"], serve["prefix_cache"],
            serve["page_size"], serve["dtype"]) == (16, 512, True, "off", 16, "bfloat16")
    assert serve["prefill_chunk"] in (64, 128)
    assert (serve["reference_check"]["prompt_len"], serve["reference_check"]["max_new_tokens"]) == (384, 64)
    assert {"deployment", "assumed", "source"} <= set(config)
    assert {"sandwich_norms", "final_norm_in_the_loop", "exit_gate", "no_bias", "a_cache_a_pass",
            "weights"} <= set(config["assumed"])


def test_opcounts_parameters_and_cache_bytes_are_the_built_trees():
    """By shape only, and from a tree of two layers (the 48 are alike; tracing
    them all costs ten seconds): no weight of the 2.67 B and no pool of the
    6.5 GB is made."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmarks.families import ouro as family
    from deepspeed_tpu.inference.serving.programs import make_slot_cache
    config = published()
    layers = config["num_hidden_layers"]
    two = dict(config, num_hidden_layers=2, layer_types=config["layer_types"][:2])
    model = family.model(two, config["serve"])
    shapes = jax.eval_shape(lambda key: nn.meta.unbox(
        model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]), jax.random.PRNGKey(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))  # noqa: E731
    a_layer = size(shapes["layers_0"])
    assert a_layer == size(shapes["layers_1"]) == opcounts_ouro.layer_params(config)
    assert size(shapes) + (layers - 2) * a_layer == opcounts_ouro.total_params(config) \
        == 2_667_974_657
    slots, positions = config["serve"]["slots"], config["serve"]["max_out_tokens"]
    cache = jax.eval_shape(lambda: make_slot_cache(model, slots, kv_quant=True))
    pools = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache["layers_0"])[0]
             if path[-1].key.startswith(("cached_key", "cached_value"))]
    # one set of leaves a layer, four passes' heads wide: 4 pools of keys and of values
    assert len(pools) == 4 and pools[0].shape == (slots, 4 * 16, 128, positions)
    held = layers * sum(leaf.size * leaf.dtype.itemsize for leaf in pools)
    assert held == slots * positions * opcounts_ouro.kv_bytes_per_token(config)
    assert opcounts_ouro.kv_bytes_per_token(config) == 798_720
    assert held == pytest.approx(6.54e9, rel=1e-3)


def test_opcounts_against_numbers_worked_by_hand():
    config, ops = published(), opcounts_ouro
    # q, k, v, o 4 x 2,048^2; gate, up, down 3 x 2,048 x 5,632; four norms
    assert ops.matmul_params_per_layer(config) == 16_777_216 + 34_603_008
    assert ops.layer_params(config) == 51_388_416
    assert ops.stack_params(config) == 2_466_643_968 + 2048
    assert ops.head_params(config) == 100_663_296
    # an int8 position of one layer in one pass: OLMoE's number
    assert ops.kv_bytes_per_position(config) == 4160
    # a decode tick streams the stack once a pass and the head once: 19.9 GB
    assert ops.decode_weight_bytes(config) == (4 * 2_466_646_016 + 100_663_296) * 2
    assert ops.decode_weight_bytes(config) == pytest.approx(19.93e9, rel=1e-3)
    chip = peaks.peaks_for("TPU v5 lite")
    # 16 slots at half their 512 positions: 3.3 GB of codes and scales over 192 walks
    kv = ops.tick_bytes(config, 16, 16 * 256) - ops.decode_weight_bytes(config)
    assert kv == 192 * (16 * 256 + 16) * 4160 and kv == pytest.approx(3.28e9, rel=1e-2)
    least, bound = ops.roofline_ms(ops.tick_flops(config, 16, 16, 16 * 256),
                                   ops.tick_bytes(config, 16, 16 * 256), chip)
    assert bound == "memory" and 27.5 < least < 29.0
    # a quarter rung of prefill, 4 slots x 128: 512 rows x 4 passes x 4.93 GFLOP a row-pass
    where = (512, 4, 4 * 150)
    flops = ops.tick_flops(config, *where)
    assert flops == pytest.approx(512 * 4 * 48 * 2 * 51_380_224, rel=0.02)
    least, bound = ops.roofline_ms(flops, ops.tick_bytes(config, 512, 4 * 150), chip)
    assert bound == "compute" and 50 < least < 54
    # one walk of one layer in one pass over those 16 slots: 17 MB, 21 us at 819 GB/s
    assert ops.pool_decode_bytes(config, 16 * 256) == 16 * 256 * 4160
    assert ops.roofline_ms(ops.pool_decode_flops(config, 4096),
                           ops.pool_decode_bytes(config, 4096), chip) == (
        pytest.approx(4096 * 4160 / 819e9 * 1e3, rel=1e-3), "memory")


def test_tick_shape_takes_the_programs_count_of_what_its_queries_attend():
    config = published()
    program = {"decode_slots_fed": 100 * 15, "decode_slots_computed": 100 * 16,
               "kv_full_positions_live_decode": 100 * 192 * 4000}
    run = {"slot_ticks": 100 * 16, "slot_ticks_busy": 100 * 16, "kv_positions_live": 100 * 4500}
    shape = ouro_ticks.tick_shape("decode", program, run, config)
    assert shape["ticks"] == 100 and shape["tokens"] == 15 and shape["positions"] == 4000
    bare = ouro_ticks.tick_shape("decode", {k: v for k, v in program.items() if "kv_" not in k},
                                 run, config)
    assert bare["positions"] == bare["kv_positions"] == pytest.approx(4500 * 15 / 16)
    least, bound, _, _ = ouro_ticks.tick_least_ms(config, shape, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 27.0 < least < 29.0
    assert ouro_ticks.tick_shape("prefill", program, run, config) is None
    # the traced slice's decode walks: 50 ticks x 192 walks x 4,000 live positions
    least_s = ouro_ticks.decode_walks_least_s(
        config, {"ticks": 50, "kv_full_positions_live": 50 * 192 * 4000},
        peaks.peaks_for("TPU v5 lite"))
    assert least_s == pytest.approx(50 * 192 * 4000 * 4160 / 819e9, rel=1e-3)


@pytest.mark.parametrize("name, counters, want", [
    ("kv_read_live_pct_looped",
     {"kv_full_positions_read_decode": 800, "kv_full_positions_live_decode": 300,
      "kv_full_positions_read_prefill": 200, "kv_full_positions_live_prefill": 100,
      "kv_full_positions_read_pass0_decode": 200, "kv_full_positions_live_pass0_decode": 75}, 40.0),
    ("loop_kv_read_gb_per_tick_looped",
     {"decode_slots_computed": 10 * 16, "kv_full_positions_read_decode": 10 * 192 * 4096},
     192 * 4096 * 4160 / 1e9),
])
def test_the_counter_readers_on_made_up_counters(monkeypatch, name, counters, want):
    reader = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    ctx = {"cell": harness.Cell(harness.REPO_ROOT,
                                harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"), LIKE)}
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    assert reader.read(ctx) == pytest.approx(want)
    monkeypatch.setattr(program_spans, "ring", lambda: ([], {"prefill_positions_fed": 5}))
    assert reader.read(ctx) is None            # the parent commit: no such counter


def test_the_program_statement_of_its_weight_stream_is_logged_beside_opcounts(monkeypatch):
    config = published()
    ticks = 50
    counters = {"loop_passes_run_decode": 4 * ticks,
                "loop_weight_bytes_streamed": ticks * opcounts_ouro.decode_weight_bytes(config)}
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    assert ouro_ticks.program_weight_bytes(config) == opcounts_ouro.decode_weight_bytes(config)
    monkeypatch.setattr(program_spans, "ring", lambda: ([], {}))
    assert ouro_ticks.program_weight_bytes(config) is None     # the parent commit


def test_the_seeded_draw_is_made_in_float32_and_scales_the_leaves_it_names():
    """The cell's ``draw`` at the test preset's shapes, served in bfloat16: every
    leaf is the package's FLOAT32 draw cast, the table and the two norms on the
    sublayers' outputs multiplied first (powers of two, so exact), nothing else."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.families import ouro as family
    config = harness.load_json(harness.REPO_ROOT, "benchmarks", "configs", "ouro-test.json")
    draw = published()["draw"]
    assert draw and all(np.log2(by) == int(np.log2(by)) for by in draw.values())
    ids, key = jnp.zeros((1, 8), jnp.int32), jax.random.PRNGKey(3)
    plain = nn.meta.unbox(family.model(config, config["serve"]).init(key, ids)["params"])
    served = dict(config["serve"], dtype="bfloat16")
    drawn = family.model(dict(config, draw=draw), served).init(key, ids)["params"]
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(drawn)[0]:
        names = [k.key for k in path]
        was = plain
        for name in names:
            was = was[name]
        by = next((draw[name] for name in names if name in draw), 1)
        seen |= {name for name in names if name in draw}
        assert was.dtype == jnp.float32 and leaf.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray((was * by).astype(jnp.bfloat16), np.float32))
    assert seen == set(draw)
    # the reference reads the drawn leaves: the family maps them, it does not redraw
    flat = family.to_reference(drawn)
    np.testing.assert_array_equal(np.asarray(flat["embed"]), np.asarray(drawn["embed_tokens"]))
    np.testing.assert_array_equal(np.asarray(flat["layers.1.ln2"]),
                                  np.asarray(drawn["layers_1"]["input_layernorm_2"]["weight"]))


def test_a_bfloat16_draw_carries_a_mean_that_a_float32_draw_cast_does_not():
    """Why the family draws in float32 and casts: ``jax.random.normal`` in
    bfloat16 has 128 values and a mean of -0.012 of its spread; along the
    all-ones direction a matrix of 2,048 inputs then gains 0.48 beside its
    random part's 1.8, the same direction in every matrix of the stack."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    key, shape, ones = jax.random.PRNGKey(5), (1024, 1024), np.ones(1024) / 32.0
    narrow = np.asarray(nn.initializers.normal(0.02)(key, shape, jnp.bfloat16), np.float64)
    assert -0.016 < narrow.mean() / narrow.std() < -0.008 and len(np.unique(narrow)) <= 256
    cast = np.asarray(nn.initializers.normal(0.02)(key, shape, jnp.float32).astype(jnp.bfloat16),
                      np.float64)
    assert abs(cast.mean() / cast.std()) < 4e-3        # 1 / 1,024: the sample's own
    assert abs(ones @ cast @ ones) < 0.1 < abs(ones @ narrow @ ones)


def test_op_label_names_the_decode_walk():
    from benchmarks.families import ouro as family
    assert family.op_label("%pool_decode.3 = bf16[16,1,2048] custom-call(...)") == "pallas:attn:decode"
    assert family.op_label("%other.1 = bf16[8] custom-call(...)") == "pallas:other"
    assert family.op_label("%fusion.7 = f32[8] fusion(...)", {"device_duration_ps": "1"}) == "fusion"

