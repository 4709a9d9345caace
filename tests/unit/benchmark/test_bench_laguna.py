"""The Laguna cell: at the test preset through ``harness.run_cell`` on the CPU
(untraced and traced, the last line held to the contract), the window control
in the program's place, its readers on made-up counters, and its operation
counts against the built tree and numbers worked by hand for the published
sizes. Nothing here is a measurement."""

import copy
import importlib.util
import json
import os

import pytest

from benchmarks.lib import harness, laguna_ticks, opcounts_laguna, peaks, program_spans

CELL, LIKE = "t-mixedlen", "serve-laguna-xs2-mixedlen-sat"
SEED = 2 ** 31 + 46
NEW = {"decode_roofline_mixedlen", "prefill_roofline_mixedlen", "moe_kernel_roofline_mixedlen",
       "moe_experts_touched_pct_mixedlen", "full_kv_read_live_pct_mixedlen",
       "ring_read_live_pct_mixedlen"}
DEVICE_ONLY = {"decode_roofline_mixedlen", "prefill_roofline_mixedlen",
               "moe_kernel_roofline_mixedlen", "moe_kernel_time_pct_sat",
               "device_idle_pct_sat"}


@pytest.fixture(scope="module")
def mixedlen_copy(bench_copy):
    """The session's copy of the benchmark with the test cell added to a
    manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "laguna-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/laguna-test.json", "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "laguna-test",
                                  "traffic": "test-mixedlen", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(mixedlen_copy):
    root, manifest = mixedlen_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs", "laguna-xs2.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, mixedlen_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(mixedlen_copy[0], mixedlen_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines, mixedlen_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(mixedlen_copy[0], mixedlen_copy[1], CELL)
    assert NEW <= {m["name"] for m in cell.per_layer}
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    # every expert is held: padding is the row tiles', no copy is another chip's
    assert metrics["moe_pad_pct_sat"]["value"] < 100
    assert "moe_elsewhere_pct_sat" not in metrics
    # 4 slots x 2 of 8: a decode tick touches some and not all of a layer's experts.
    # The counters are the process's: another file's schedulers that ran in this
    # worker count decode ticks and no touched experts (6.07 in the driver's run of
    # the whole suite for the 39.0 this cell reads alone), so only the sign is held
    # here and the arithmetic by the made-up counters below
    assert 0 < metrics["moe_experts_touched_pct_mixedlen"]["value"] < 100
    # a decode tick reads every slot's pool as far as the longest goes
    assert 5 < metrics["full_kv_read_live_pct_mixedlen"]["value"] <= 100
    # 8 of a ring's 128 positions are a query's window at the most, 15 a chunk's
    assert 0 < metrics["ring_read_live_pct_mixedlen"]["value"] <= 100 * 15 / 128
    assert metrics["recompiles_in_window_sat"]["value"] == 0


def test_the_window_control_stands_in_the_programs_place(mixedlen_copy):
    """``tools/dots3_note_controls.py`` ``full_window`` at the test preset: a
    server built as the cell builds it whose sliding layers attend every
    earlier position over pools of the full extent, held to the plain
    reference by the runner's own comparison (in float32 on eight tokens
    nothing here is a chip's reading)."""
    spec = importlib.util.spec_from_file_location(
        "dots3_note_controls", os.path.join(harness.REPO_ROOT, "tools", "dots3_note_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(mixedlen_copy[0], mixedlen_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, "full_window")))
    assert line["tol"] == cell.config["serve"]["reference_check"]["logit_gap_tol"]
    # another forward pass: small on eight tiny tokens, and not the program's zero
    assert line["worst_logit_gap"] > 1e-4
    assert cell.config["serve"]["slots"] == 4          # the control's own copy was changed


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "laguna"
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 960}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2048, "sigma": 1.2, "min": 256,
                                 "max": 15360}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64,
                                 "max": 1024}
    assert (mix["max_total"], mix["preroll_s"], mix["drain_s"], mix["trace_seconds"]) == (16384, 20, 0, 4)
    assert mix["block"] == 16     # the issue's one remedy for the seeds' spread (it wrote 32)
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    assert NEW <= {m["name"] for m in cell.per_layer}
    assert not [m["name"] for m in cell.per_layer
                if m["name"].endswith(("_agent", "_reason", "_longdoc", "_longctx"))]
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                                 "num_attention_heads_per_layer"]
    assert config["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["published"]["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    # no width is cut, nor the experts, the experts a token, the vocabulary
    assert (config["hidden_size"], config["head_dim"], config["num_key_value_heads"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["vocab_size"], config["sliding_window"]) == (
                2048, 128, 8, 8192, 512, 512, 256, 8, 100352, 512)
    assert config["serve"]["slots"] == 32 and config["serve"]["max_out_tokens"] == 16384
    assert {"deployment", "assumed", "published", "source"} <= set(config)


def test_opcounts_parameters_are_the_built_trees_leaves():
    """By shape only: no weight of the 3.87 B is made."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmarks.families import laguna as family
    config = published()
    model = family.model(config, config["serve"])
    shapes = jax.eval_shape(lambda key: nn.meta.unbox(
        model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]), jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(leaf.size for leaf in leaves) == opcounts_laguna.params(config) == 3_869_857_792
    heads = [shapes[f"layers_{i}"]["self_attn"]["q_proj"]["kernel"].shape for i in range(5)]
    assert heads == [(2048, h, 128) for h in (48, 64, 64, 64, 48)]
    assert model.config.window_ring == family.window_ring(config, config["serve"])
    assert model.config.window_ring >= 511 + config["serve"]["prefill_chunk"]


def test_opcounts_against_numbers_worked_by_hand():
    config = published()
    ops = opcounts_laguna
    assert [ops.layers(config, k) for k in "FWDE"] == [2, 3, 1, 4]
    # q and o 2 x 2,048 x 6,144, k and v 2 x 2,048 x 1,024, the gate 2,048 x 48, two norms
    assert ops.attention_params(config, 0) == 29_458_432 + 4096
    assert ops.attention_params(config, 1) == 37_879_808 + 4096
    assert ops.dense_ffn_params(config) == 50_331_648 and ops.expert_params(config) == 3_145_728
    assert ops.head_params(config) == 2048 * 100352 + 2048
    # 32 tokens x 8 of 256: 256 (1 - (31/32)^32) = 163.3 experts of a layer, 63.8%
    assert ops.experts_touched(config, 32) == pytest.approx(163.3, abs=0.1)
    # an int8 position: keys and values of 8 heads, 128 codes and a bf16 scale each
    assert ops.kv_bytes_per_position(config) == 2080
    # the pool of the two full layers at 32 slots x 16,384: 2.18 GB
    assert 2 * 32 * 16384 * 2080 == pytest.approx(2.18e9, rel=2e-3)
    assert ops.expert_flops(config, 32) == 32 * 8 * 4 * 2 * 3_145_728
    assert ops.expert_bytes(config, 32, touched=163) == 4 * 163 * 3_145_728 * 2
    # a decode tick of 32 slots at 4,000 live positions: the experts over half its bytes
    nbytes = ops.tick_bytes(config, 32, 32, 32 * 4000, 32 * 512, touched=163)
    assert 0.5 < ops.expert_bytes(config, 32, 163) / nbytes < 0.9
    chip = peaks.peaks_for("TPU v5 lite")
    least, bound = ops.roofline_ms(ops.tick_flops(config, 32, 32, 32 * 4000, 32 * 512), nbytes, chip)
    assert bound == "memory" and 6.0 < least < 7.5
    # a full quarter-rung prefill tick reaches every expert (6.4 GB of the four layers'
    # experts for 1.4 TFLOP): still bound by memory; the whole program's 8,192 tokens by compute
    where = (2048, 8, 8 * 3000, 8 * 767)
    assert ops.roofline_ms(ops.tick_flops(config, *where), ops.tick_bytes(config, *where), chip)[1] == "memory"
    where = (8192, 32, 32 * 3000, 32 * 767)
    assert ops.roofline_ms(ops.tick_flops(config, *where), ops.tick_bytes(config, *where), chip)[1] == "compute"
    full, window = ops.attention_pairs(config, 256, 1, 3000, 767)
    assert full == pytest.approx(256 * (3000 - 127.5)) and window == 256 * 512


def test_tick_shape_takes_the_programs_counts_of_what_its_queries_attend():
    config = published()
    program = {"decode_slots_fed": 3000, "decode_slots_computed": 3200,
               "moe_rows_routed_decode": 100 * 30 * 8 * 4, "moe_experts_touched_decode": 100 * 600,
               "kv_full_positions_live_decode": 100 * 2 * 90000,
               "kv_ring_positions_live_decode": 100 * 3 * 15000}
    run = {"slot_ticks": 100 * 32, "slot_ticks_busy": 100 * 31, "kv_positions_live": 100 * 95000}
    shape = laguna_ticks.tick_shape("decode", program, run, config)
    assert shape["ticks"] == 100 and shape["tokens"] == 30 and shape["touched"] == 600
    assert shape["full_positions"] == 90000 and shape["window_positions"] == 15000
    bare = laguna_ticks.tick_shape("decode", {k: v for k, v in program.items() if "kv_" not in k},
                                   run, config)
    assert bare["full_positions"] == bare["kv_positions"]
    assert bare["window_positions"] == min(bare["kv_positions"], 30 * 512)
    least, bound, _, _ = laguna_ticks.tick_least_ms(config, shape, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and 5.0 < least < 8.0
    one = laguna_ticks.moe_kernels_least_s(config, program, run, peaks.peaks_for("TPU v5 lite"),
                                           {"decode": 1})
    assert 0.004 < one < 0.006          # 600 experts' 3.77 GB at 819 GB/s
    assert laguna_ticks.tick_shape("prefill", program, run, config) is None


@pytest.mark.parametrize("name, counters, want", [
    ("moe_experts_touched_pct_mixedlen",
     {"decode_slots_computed": 320, "moe_experts_touched_decode": 10 * 4 * 160}, 62.5),
    ("full_kv_read_live_pct_mixedlen",
     {"kv_full_positions_read_decode": 800, "kv_full_positions_live_decode": 300,
      "kv_full_positions_read_prefill": 200, "kv_full_positions_live_prefill": 100}, 40.0),
    ("ring_read_live_pct_mixedlen",
     {"kv_ring_positions_read_decode": 768, "kv_ring_positions_live_decode": 512}, 100 * 512 / 768),
])
def test_the_counter_readers_on_made_up_counters(monkeypatch, name, counters, want):
    reader = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", name + ".py")
    ctx = {"cell": harness.Cell(harness.REPO_ROOT,
                                harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"), LIKE)}
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    assert reader.read(ctx) == pytest.approx(want)
    monkeypatch.setattr(program_spans, "ring", lambda: ([], {"prefill_positions_fed": 5}))
    assert reader.read(ctx) is None            # the parent commit: no such counter


def test_op_label_names_the_experts_kernels():
    from benchmarks.families import laguna as family
    assert family.op_label("%gmm.3 = bf16[256,512] custom-call(...)") == "pallas:moe:matmul"
    assert family.op_label("%other.1 = bf16[8] custom-call(...)") == "pallas:other"
    assert family.op_label("%fusion.7 = f32[8] fusion(...)", {"device_duration_ps": "1"}) == "fusion"


def test_the_seeded_draw_scales_the_routed_down_projections_and_nothing_else():
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.families import laguna as family
    draw = published()["draw"]
    assert draw == {"routed_down_proj": 0.25}
    leaf = jnp.asarray([[0.02, -0.0137], [1.5, 0.25]], jnp.bfloat16)
    bank = {"experts": {"deepspeed_experts": {"down_proj": {"kernel": leaf},
                                              "up_proj": {"kernel": leaf}}},
            "shared_expert": {"down_proj": {"kernel": leaf}}}
    plain = {"embed_tokens": leaf, "layers_1": {"moe": {"deepspeed_moe": bank}},
             "layers_0": {"mlp": {"down_proj": {"kernel": leaf}}}}
    got = family.scaled_draw(plain, draw)
    as_f32 = lambda t: np.asarray(t.astype(jnp.float32))  # noqa: E731
    moe = got["layers_1"]["moe"]["deepspeed_moe"]
    np.testing.assert_array_equal(
        as_f32(moe["experts"]["deepspeed_experts"]["down_proj"]["kernel"]), as_f32(leaf) / 4)
    for same in (moe["experts"]["deepspeed_experts"]["up_proj"]["kernel"],
                 moe["shared_expert"]["down_proj"]["kernel"], got["embed_tokens"],
                 got["layers_0"]["mlp"]["down_proj"]["kernel"]):
        np.testing.assert_array_equal(as_f32(same), as_f32(leaf))
