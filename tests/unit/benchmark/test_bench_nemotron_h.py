"""The Nemotron-H cell: at the test preset through ``harness.run_cell`` on
the CPU (untraced and traced, the last line held to the contract), its
readers on made-up counters, and its operation counts against numbers
worked by hand for the published sizes. Nothing here is a measurement."""

import copy
import json
import os

import pytest

from benchmarks.lib import harness, nemotron_h_ticks, opcounts_nemotron_h, peaks, program_spans

CELL, LIKE = "t-reason", "serve-nemotron-3-super-reason-sat"
SEED = 2 ** 31 + 30
DEVICE_ONLY = {"decode_roofline_reason", "prefill_roofline_reason", "moe_kernel_time_pct_sat",
               "moe_kernel_roofline_reason", "device_idle_pct_sat"}


@pytest.fixture(scope="module")
def reason_copy(bench_copy):
    """The session's copy of the benchmark with the Nemotron-H test cell
    added to a manifest of its own: new entries only."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    manifest["configs"].append({"name": "nemotron-h-test", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/nemotron-h-test.json", "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "nemotron-h-test",
                                  "traffic": "test-reason", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(reason_copy):
    root, manifest = reason_copy
    return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced, require_tpu=False)
            for traced in (0, 1)}


def published():
    with open(os.path.join(harness.REPO_ROOT, "benchmarks", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, reason_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(reason_copy[0], reason_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"serve_total_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines, reason_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(reason_copy[0], reason_copy[1], CELL)
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    # a quarter of the experts is held: three copies in four are another chip's
    assert 60 < metrics["moe_elsewhere_pct_sat"]["value"] < 90
    assert 0 < metrics["moe_pad_pct_sat"]["value"] < 100
    assert 0 < metrics["prefill_fill_pct_sat"]["value"] <= 100
    # the counters are the process's (another file's schedulers may have run
    # in it): 4 slots x 2 layers x ~19 KB of state and tail, read + write,
    # is 0.00017 GB a tick here
    assert 0 < metrics["ssm_state_gb_per_tick_reason"]["value"] < 0.01
    assert metrics["recompiles_in_window_sat"]["value"] == 0


def test_the_seeded_weights_are_the_familys_and_the_packages_draw_stays_plain(reason_copy):
    """``assumed.weights``: the benchmark's model centres every relu^2 MLP's
    down projection over its hidden axis; the package's own initialiser does
    not (a benchmark's concern stays out of the model layer)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    cell = harness.Cell(reason_copy[0], reason_copy[1], CELL)
    seeded = cell.family.model(cell.config, cell.config["serve"])
    plain = type(seeded).__mro__[1](seeded.config)
    made = [nn.meta.unbox(m.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"])
            for m in (seeded, plain)]
    assert jax.tree.structure(made[0]) == jax.tree.structure(made[1])
    downs = 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(made[0])[0], jax.tree.leaves(made[1])):
        if [getattr(k, "key", None) for k in path][-2:] == ["down_proj", "kernel"]:
            downs += 1
            np.testing.assert_allclose(np.asarray(a).mean(axis=-2), 0.0, atol=1e-7)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b - b.mean(axis=-2, keepdims=True)),
                                       atol=1e-7)
            assert np.abs(np.asarray(b).mean(axis=-2)).max() > 1e-4
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert downs == 4   # two expert layers: a bank and a shared expert each


@pytest.mark.parametrize("control", ["program", "fp8_weights", "bf16_state"])
def test_controls_stand_in_the_programs_place(reason_copy, control):
    """``tools/nemotron_h_controls.py`` at the test preset: each control is a
    server built as the cell builds it, held to the plain reference by the
    runner's own comparison. In float32 on eight tokens that comparison
    passes all three (nothing here is a chip's reading); the carried state
    against the reference's final state tells them apart."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_controls", os.path.join(harness.REPO_ROOT, "tools", "nemotron_h_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(reason_copy[0], reason_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, control)))
    assert {"worst_logit_gap", "tol", "ok", "state_rel_err", "first_layer_state_rel_err"} <= set(line)
    assert line["tol"] == cell.config["serve"]["reference_check"]["logit_gap_tol"]
    if control == "program":
        assert line["ok"] is True and line["state_rel_err"] < 1e-5
    else:
        assert line["first_layer_state_rel_err"] > 1e-3
        assert line.get("weights_are_fp8_values", True) is True


def test_the_schedule_model_gives_what_the_chip_read():
    """``tools/serve_schedule_model.py`` at the cell's two tick times, on
    the six seeds the chip ran at ``block`` 64 (3,425.9 3,462.9 3,379.5
    3,432.8 3,358.6 3,365.4 tokens/s, PERF.md section 6, PR 30): within half
    a percent each, and steadier at the ``block`` the cell now has."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_schedule_model", os.path.join(harness.REPO_ROOT, "tools", "serve_schedule_model.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    cell = harness.Cell(harness.REPO_ROOT, harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"), LIKE)
    seeds = range(3000000511, 3000000517)
    read = [3425.9, 3462.9, 3379.5, 3432.8, 3358.6, 3365.4]
    at_64 = [model.run_seed(cell, s, 0.0187, 0.3438, block=64) for s in seeds]
    assert all(abs(got / want - 1.0) < 0.005 for got, want in zip(at_64, read))
    at_16 = [model.run_seed(cell, s, 0.0187, 0.3438) for s in seeds]
    assert model.spread_pct(at_16) < 0.75 * model.spread_pct(at_64)


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.config["family"] == "nemotron_h"
    mix = cell.traffic
    assert mix["arrivals"] == {"process": "all_at_zero", "count": 960}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert (mix["max_total"], mix["block"], mix["preroll_s"], mix["drain_s"],
            mix["trace_seconds"]) == (2048, 16, 20, 0, 4)   # block: the chip changed the issue's 64
    assert [m["name"] for m in cell.end_to_end] == ["serve_total_tok_s", "setup_s"]
    for metric in cell.per_layer:
        path = os.path.join(harness.REPO_ROOT, "benchmarks", "layer_metrics", metric["name"])
        assert os.path.exists(path + ".py") or os.path.exists(path + ".json")
        # a cell's entries move its end-to-end metric or ``setup_s``; one it shares lists the others
        assert metric["moves"] in ("serve_total_tok_s", "setup_s")
        if metric["name"].endswith("_reason"):                   # the family's own: this cell alone
            assert metric["moves"] == "serve_total_tok_s" and metric["workloads"] == [LIKE]
    assert sum(m["name"].endswith("_reason") for m in cell.per_layer) == 5


def test_the_configuration_is_the_catalogs_but_for_what_it_lists():
    """No width differs from the published config; the cut is the depth
    (one whole period), the experts held and the vocabulary slice, each
    with its published value beside it."""
    config = published()
    assert config["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    was = config["published"]
    assert (was["num_hidden_layers"], was["n_routed_experts"], was["vocab_size"]) == (88, 512, 131072)
    assert was["hybrid_override_pattern"].startswith(config["hybrid_override_pattern"])
    assert len(was["hybrid_override_pattern"]) == 88
    assert [was["hybrid_override_pattern"].count(k) for k in "ME*"] == [40, 40, 8]
    assert config["hybrid_override_pattern"] == "MEMEMEM*EME" and config["num_hidden_layers"] == 11
    assert config["experts_held"] == [0, 128] and config["n_routed_experts"] == 128
    assert config["n_routed_experts_published"] == 512 and config["vocab_size"] == 32768
    widths = dict(hidden_size=4096, mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
                  ssm_state_size=128, conv_kernel=4, chunk_size=128, num_attention_heads=32,
                  num_key_value_heads=2, head_dim=128, moe_latent_size=1024,
                  moe_intermediate_size=2688, moe_shared_expert_intermediate_size=5376,
                  num_experts_per_tok=22, routed_scaling_factor=5)
    assert {k: config[k] for k in widths} == widths
    serve = config["serve"]
    assert (serve["slots"], serve["prefill_chunk"], serve["max_out_tokens"], serve["kv_quant"],
            serve["prefix_cache"]) == (64, 128, 2048, True, "off")
    assert serve["reference_check"]["prompt_len"] == 1000
    assert serve["reference_check"]["max_new_tokens"] == 256


@pytest.mark.parametrize("reader, counters, want", [
    ("moe_pad_pct_sat", {"moe_rows_routed": 600, "moe_rows_computed": 800}, 25.0),
    ("moe_pad_pct_sat", {"prefill_positions_fed": 5}, None),     # the parent, a dense model
    ("moe_elsewhere_pct_sat", {"moe_rows_routed": 250, "moe_rows_elsewhere": 750}, 75.0),
    ("moe_elsewhere_pct_sat", {"moe_rows_routed": 250}, None),   # OLMoE counts no elsewhere
    ("ssm_state_gb_per_tick_reason",
     {"ssm_state_bytes_touched": 3 * 2_000_000_000, "decode_slots_computed": 2 * 64,
      "prefill_positions_computed": 64 * 128}, 2.0),
    ("ssm_state_gb_per_tick_reason", {"decode_slots_computed": 128}, None),
])
def test_counter_readers_on_made_up_counters(monkeypatch, reader, counters, want):
    module = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics", reader + ".py")
    monkeypatch.setattr(program_spans, "ring", lambda: ([], counters))
    cell = harness.Cell(harness.REPO_ROOT, harness.load_json(harness.REPO_ROOT, "BENCHMARK.json"),
                        LIKE)
    got = module.read({"cell": cell})
    assert got is None if want is None else got == pytest.approx(want)


def test_tick_roofline_on_made_up_counters():
    config = published()
    serve = config["serve"]
    # 100 decode ticks that fed 60 of 64 slots, 10 prefill ticks a quarter full;
    # 110 working ticks with 62 slots busy holding 1,000 positions each
    program = {"decode_slots_fed": 6000, "decode_slots_computed": 6400,
               "prefill_positions_fed": 10 * 2048, "prefill_positions_computed": 10 * 64 * 128}
    run = {"slot_ticks": 110 * 64, "slot_ticks_busy": 110 * 62, "kv_positions_live": 110 * 62000}
    decode = nemotron_h_ticks.tick_shape("decode", program, run, serve)
    assert decode["ticks"] == 100 and decode["tokens"] == 60 and decode["sequences"] == 60
    assert nemotron_h_ticks.ticks_run(program, serve) == 110
    chip = peaks.peaks_for("TPU v5 lite")
    least, bound, flops, nbytes = nemotron_h_ticks.tick_least_ms(config, decode, chip)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9 * 1e3)
    # the fed slots' state twice, 60 x 2 x 21.28 MB = 2.55 GB; ~119 of 128 experts touched a layer
    assert 2 * 60 * opcounts_nemotron_h.state_bytes_per_slot(config) == pytest.approx(2.553e9, rel=1e-3)
    assert 13.0 < least < 14.5
    full = {"tokens": 8192, "sequences": 64, "kv_positions": 32000}
    assert nemotron_h_ticks.tick_least_ms(config, full, chip)[1] == "compute"
    one = nemotron_h_ticks.moe_kernels_least_s(config, program, run, chip, {"decode": 1})
    both = nemotron_h_ticks.moe_kernels_least_s(config, program, run, chip,
                                                {"decode": 3, "prefill": 2})
    assert 0.006 < one < 0.009 and both > 5 * one


def test_opcounts_against_numbers_worked_by_hand():
    """Published sizes, one period, 128 of 512 experts, a quarter of the vocabulary."""
    config = published()
    ops = opcounts_nemotron_h
    assert [ops.layers(config, k) for k in "ME*"] == [5, 5, 1]
    # in_proj 4096 x (2 x 8192 + 2 x 8 x 128 + 128), the conv's 5 x 10,240, three
    # vectors a head, the gated norm's 8192, out_proj 8192 x 4096, the block's norm
    assert ops.mamba_params(config) == (4096 * 18560 + 5 * 10240 + 3 * 128 + 8192
                                        + 8192 * 4096 + 4096) == 109_640_064
    assert ops.attention_params(config) == 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096 == 35_655_680
    assert ops.expert_params(config) == 2 * 1024 * 2688 == 5_505_024
    assert ops.moe_shared_params(config) == (4096 * 512 + 512 + 2 * 4096 * 1024
                                             + 2 * 4096 * 5376 + 4096) == 54_530_560
    assert ops.params_held(config) == pytest.approx(4.648e9, rel=1e-3)      # 9.30 GB of bf16
    # a slot: 5 layers x (128 x 64 x 128 float32 + 3 x 10,240 bf16)
    assert ops.state_bytes_per_slot(config) == 5 * (4_194_304 + 61_440) == 21_278_720
    assert ops.kv_bytes_per_position(config) == 520
    assert ops.picks_here(config) == 5.5
    assert ops.experts_touched(config, 1) == pytest.approx(5.5)
    assert ops.experts_touched(config, 64) == pytest.approx(120.3, abs=0.1)
    assert ops.expert_flops(config, 8192) == 5 * 8192 * 5.5 * 2 * 5_505_024
    assert ops.scan_flops_per_token(config) == 5 * 128 * 64 * 128 + 2 * 4 * 10240
    least, bound = ops.roofline_ms(197e12 * 0.01, 819e9 * 0.02, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(20.0)
