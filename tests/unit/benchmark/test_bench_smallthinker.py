"""The SmallThinker training cell: at the test preset through
``harness.run_cell`` on the CPU (untraced and traced, the last line held to
the contract), its controls in the program's place, its readers on the
program's own counts, and its operation counts against the issue's numbers
worked by hand for the published sizes. Nothing here is a measurement."""

import copy
import importlib.util
import json
import os
import sys

import pytest

from benchmarks.lib import harness, opcounts_smallthinker as ops

CELL, LIKE = "t-moe16k", "train-smallthinker-21b-a3b-seq16k"
SEED = 2 ** 31 + 39
DEVICE_ONLY = {"train_mfu_pct_moe16k", "train_peak_hbm_gb", "device_idle_pct_train",
               "flash_attn_time_pct_moe16k", "flash_attn_roofline_moe16k",
               "moe_kernel_time_pct_moe16k", "moe_kernel_roofline_moe16k"}


@pytest.fixture(scope="module")
def moe16k_copy(bench_copy):
    """The session's copy of the benchmark with the test cell added to a
    manifest of its own: new entries only. The test preset holds 4 of its 8
    experts (the file alone holds all, for the model's own tests)."""
    root, manifest = bench_copy
    manifest = copy.deepcopy(manifest)
    config = harness.load_json(root, "benchmarks", "configs", "smallthinker-test.json")
    config.update(experts_held=[2, 4], moe_num_primary_experts=4,
                  moe_num_primary_experts_published=8)
    with open(os.path.join(root, "benchmarks", "configs", "smallthinker-test-held.json"), "w") as f:
        json.dump(config, f)
    manifest["configs"].append({"name": "smallthinker-test-held", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/smallthinker-test-held.json",
                                "why": "tests"})
    manifest["workloads"].append({"name": CELL, "config": "smallthinker-test-held",
                                  "traffic": "test-pretrain-seq16k", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    return root, manifest


@pytest.fixture(scope="module")
def lines(moe16k_copy):
    """Both runs on a recorder of their own, as a benchmark process has it:
    the counters the cell's readers share names with the serving scheduler's
    (``moe_rows_routed``), which other tests of the worker have filled."""
    from deepspeed_tpu.utils import trace as program_trace

    root, manifest = moe16k_copy
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(program_trace, "_RECORDER", program_trace.Recorder())
        return {traced: harness.run_cell(root, manifest, CELL, SEED, 0.5, traced,
                                         require_tpu=False)
                for traced in (0, 1)}


def published():
    return harness.load_json(harness.REPO_ROOT, "benchmarks", "configs",
                             "smallthinker-21b-a3b.json")


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_keeps_the_contract(lines, moe16k_copy, traced):
    line = json.loads(json.dumps(lines[traced]))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    cell = harness.Cell(moe16k_copy[0], moe16k_copy[1], CELL)
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"]
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_traced_run_reads_the_program_and_leaves_device_numbers_out(lines, moe16k_copy):
    metrics = lines[1]["metrics"]
    cell = harness.Cell(moe16k_copy[0], moe16k_copy[1], CELL)
    assert set(metrics) == {m["name"] for m in cell.per_layer} - DEVICE_ONLY
    # half of the experts is held: about one copy in two is another chip's
    assert 20 < metrics["moe_elsewhere_pct_moe16k"]["value"] < 80
    # 32 positions are one tile of the kernels: the window skips none of it
    # (the cell's geometry is the test below)
    assert metrics["window_tiles_skipped_pct_moe16k"]["value"] == 0.0
    assert metrics["recompiles_in_window"]["value"] == 0
    assert metrics["train_step_ms_p50"]["value"] > 0
    # the counts the elsewhere share reads, as metrics of their own
    assert 0 <= metrics["moe_pad_pct_moe16k"]["value"] < 100
    assert metrics["moe_load_max_over_mean_moe16k"]["value"] >= 1.0
    assert metrics["setup_backend_load_s"]["value"] >= 0
    assert metrics["setup_engine_init_s"]["value"] > 0


def test_window_tiles_skipped_at_the_cells_geometry(monkeypatch):
    """The kernels' own tile walks (``_count_tiles``, forward, dq and dkv) at
    16,384 positions, a window of 4,096 on three layers of four and the tiles
    the package resolves for the cell's shapes, through the cell's reader: the
    geometry's 42% (56% on a window layer), to a tile's rounding."""
    import jax.numpy as jnp

    from benchmarks.lib import smallthinker_steps
    import deepspeed_tpu.ops.pallas.flash_attention  # noqa: F401  (the package exports the function)
    fa = sys.modules["deepspeed_tpu.ops.pallas.flash_attention"]
    from deepspeed_tpu.ops.pallas.attention_geometry import resolve_geometry
    from deepspeed_tpu.utils.trace import Recorder

    rec = Recorder()
    monkeypatch.setattr(fa, "recorder", lambda: rec)
    seq, heads = 16384, 28
    geom, _ = resolve_geometry(seq, seq, 128, heads, 1, True, jnp.bfloat16)
    for window in (None, 4096, 4096, 4096):
        static = dict(off=0, causal=True, window=window)
        tq, tk = fa._tiles(geom.block_q, geom.block_k, geom.tile)
        fa._count_tiles(fa._k_walk, seq // tq, tq, tk, seq // tk, heads, **static)
        tq, tk, dkv_tq, dkv_tk = fa._bwd_tiles(seq, seq, geom.block_q_bwd, geom.block_k_bwd,
                                               geom.tile, False)
        fa._count_tiles(fa._k_walk, seq // tq, tq, tk, seq // tk, heads, **static)
        fa._count_tiles(fa._q_walk, seq // dkv_tk, dkv_tk, dkv_tq, seq // dkv_tq, heads, **static)
    monkeypatch.setattr(smallthinker_steps.program_spans, "ring", lambda: ([], dict(rec.counters)))
    reader = harness.load_module(harness.REPO_ROOT, "benchmarks", "layer_metrics",
                                 "window_tiles_skipped_pct_moe16k.py")
    skipped = reader.read({})
    # in tiles of 512 a window of 8 tiles touches 9, and a full layer's query
    # tile 16.5 of 32 on average: 1 - 7.875 / 16.5 = 52.3% a window layer,
    # 39.2% over the period, under the pairs' 56.25% and 42.2% by that tile
    assert 38.5 < skipped <= 100 * 0.75 * 0.5625
    window, full, walks_w, walks_f = smallthinker_steps.window_tiles()
    assert walks_w == 3 * walks_f and 0.50 < 1 - window / full <= 0.5625


def test_op_label_names_the_kernels_as_the_compiled_program_does():
    """Instruction names as the sandbox's compile for the chip gives them
    (``tests/unit/ops/test_tpu_compile.py``): megablox's kernels carry what
    differentiated them around ``gmm`` / ``tgmm``."""
    family = harness.load_module(harness.REPO_ROOT, "benchmarks", "families", "smallthinker.py")
    call = "%{} = bf16[8,128]{{1,0}} custom-call(%p0), custom_call_target=\"tpu_custom_call\""
    for name, label in [("flash_fwd.7", "pallas:flash:fwd"), ("flash_bwd_dq.1", "pallas:flash:dq"),
                        ("flash_bwd_dkv.1", "pallas:flash:dkv"), ("gmm.3", "pallas:moe:matmul"),
                        ("jvp_jit_gmm__.5", "pallas:moe:matmul"),
                        ("transpose_jvp_jit_gmm___.2", "pallas:moe:matmul"),
                        ("transpose_jvp_jit_tgmm___.2", "pallas:moe:matmul"),
                        ("some_kernel.2", "pallas:other")]:
        assert family.op_label(call.format(name)) == label, name
    assert family.op_label("%fusion.12 = bf16[8]{0} fusion(%p0), kind=kLoop") == "fusion"


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it():
    manifest = harness.load_json(harness.REPO_ROOT, "BENCHMARK.json")
    cell = harness.Cell(harness.REPO_ROOT, manifest, LIKE)
    assert cell.chips == 1 and cell.entry["config"] == "smallthinker-21b-a3b"
    assert cell.entry["traffic"] == "pretrain-seq16k" and len(cell.entry["why"]) <= 200
    assert cell.traffic["seq_len"] == 16384 and cell.traffic["ring"] == 8
    assert [m["name"] for m in cell.end_to_end] == ["train_tok_s_chip", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    # the train entries and those under ``setup_s`` that the cell shares; a reader that
    # differs has an entry of the cell's own, which lists this cell alone
    shared = ["recompiles_in_window", "train_step_ms_p50", "train_peak_hbm_gb",
              "device_idle_pct_train", "train_host_ms_p50", "setup_trace_lower_s",
              "setup_backend_load_s", "setup_cache_misses", "setup_programs_loaded",
              "setup_engine_init_s", "setup_import_s"]
    assert set(shared) <= {n for n in names if not n.endswith("_moe16k")}
    assert len(manifest["per_layer"]) <= 128        # the contract's limit
    assert all(m["workloads"] == [LIKE] for m in cell.per_layer if m["name"].endswith("_moe16k"))
    assert {m["moves"] for m in cell.per_layer} == {"train_tok_s_chip", "setup_s"}
    entry = [c for c in manifest["configs"] if c["name"] == "smallthinker-21b-a3b"][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout", "moe_num_primary_experts",
        "vocab_size"]


def test_the_configuration_keeps_every_published_width():
    config = published()
    for key, value in dict(hidden_size=2560, num_attention_heads=28, num_key_value_heads=4,
                           head_dim=128, moe_ffn_hidden_size=768,
                           moe_num_primary_experts_published=64,
                           moe_num_active_primary_experts=6, sliding_window_size=4096,
                           rope_theta=1500000, rms_norm_eps=1e-6,
                           max_position_embeddings=16384).items():
        assert config[key] == value, key
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["rope_layout"][:4] == config["rope_layout"] == [0, 1, 1, 1]
    assert config["published"]["moe_num_primary_experts"] == 64
    assert config["published"]["vocab_size"] == 4 * config["vocab_size"] == 151936


def test_params_held_is_the_built_trees_leaves():
    import jax
    import jax.numpy as jnp

    config = published()
    assert ops.params_held(config) == 656_529_920
    family = harness.load_module(harness.REPO_ROOT, "benchmarks", "families", "smallthinker.py")
    model = family.model(config, config["train"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    leaves = jax.tree.leaves(shapes["params"])
    assert sum(int(leaf.size) for leaf in leaves) == 656_529_920


def test_opcounts_against_numbers_worked_by_hand():
    config, seq = published(), 16384
    assert ops.layer_params_outside_experts(config) == 21_140_480
    assert ops.expert_params(config) == 5_898_240
    # a full layer's query sees (L + 1) / 2 keys on average, a window layer's
    # (4096 * 4097 / 2 + 12288 * 4096) / 16384 = 3584.125
    assert ops.live_pairs(seq) == seq * (seq + 1) // 2
    assert ops.live_pairs(seq, 4096) / seq == pytest.approx(3584.125)
    assert 1 - ops.live_pairs(seq, 4096) / ops.live_pairs(seq) == pytest.approx(0.5625, abs=1e-3)
    # the issue's reckoning: ~0.70 GFLOP a token forward, of which attention
    # over live positions 0.27, held experts 0.07, head 0.19
    attn = 4 * 28 * 128 * ops.attention_pairs(config, seq) / seq
    assert attn / 1e9 == pytest.approx(0.2716, abs=1e-3)
    assert ops.even_rows_per_token(config) == 1.5
    assert 4 * 1.5 * 2 * ops.expert_params(config) / 1e9 == pytest.approx(0.0708, abs=1e-3)
    assert 2 * ops.head_params(config) / 1e9 == pytest.approx(0.1945, abs=1e-3)
    assert ops.forward_flops_per_token(config, seq) / 1e9 == pytest.approx(0.706, abs=2e-3)
    assert ops.train_flops_per_token(config, seq) * 32768 / 1e12 == pytest.approx(69.4, abs=0.3)
    # counted rows in the even router's place move the experts' term alone
    more = ops.forward_flops_per_token(config, seq, 3.0) - ops.forward_flops_per_token(config, seq)
    assert more == pytest.approx(4 * 1.5 * 2 * ops.expert_params(config))
    # kernels: flash over live pairs, forward + backward = 3 x the forward's
    assert ops.flash_flops(config, 2, seq) == pytest.approx(3 * 2 * seq * attn)
    # 3,072 rows an expert a step: one 3.9 MB projection is compute-bound
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    rows = 32768 * 1.5 * 4
    least, bound = ops.roofline_seconds(ops.moe_kernel_flops(config, rows),
                                        ops.moe_kernel_bytes(config, rows, 4), peaks)
    assert bound == "compute" and least == pytest.approx(3 * rows * 2 * 5_898_240 / 197e12)


@pytest.mark.parametrize("control", ["program", "fp8_experts", "full_window"])
def test_controls_stand_in_the_programs_place(moe16k_copy, control):
    """``tools/smallthinker_controls.py`` at the test preset: each control is
    the runner's own ``run`` over a one-step window with one thing changed
    from outside, and ``ok`` is the ``correct`` it returned (on a 64-wide model
    nothing here is a chip's reading; the limits are the test preset's loose
    ones)."""
    spec = importlib.util.spec_from_file_location(
        "smallthinker_controls",
        os.path.join(harness.REPO_ROOT, "tools", "smallthinker_controls.py"))
    controls = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(controls)
    cell = harness.Cell(moe16k_copy[0], moe16k_copy[1], CELL)
    line = json.loads(json.dumps(controls.run_control(cell, SEED, control)))
    assert {"loss", "grad_norm", "ok"} <= set(line)
    assert line["loss"]["rtol"] == cell.config["train"]["reference_check"]["loss_rtol"]
    # the verdict is the runner's own, of the numbers beside it
    assert line["ok"] == all(line[k]["rel_diff"] <= line[k]["rtol"] for k in ("loss", "grad_norm"))
    assert cell.runner._model.__module__ == cell.runner._check_against_reference.__module__
    if control == "program":
        assert line["ok"] is True
    else:
        # the altered step is another step: its loss or its gradient's norm
        # moves further from the reference than the program's own
        program = controls.run_control(cell, SEED, "program")
        moved = max(line[k]["rel_diff"] / max(program[k]["rel_diff"], 1e-9)
                    for k in ("loss", "grad_norm"))
        assert moved > 1.0
