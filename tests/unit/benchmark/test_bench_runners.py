"""Each runner end to end at the ``test`` preset on the CPU, through the
harness's ``run_cell`` and a copy of the benchmark to which the test cells
were added as new files and entries only; the last line is held to the
contract. Nothing here is a measurement: the platform is the CPU and the
line says so."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import harness

# the cells conftest.py's ``bench_copy`` adds, with the chips each takes
TEST_CELLS = {"t-train": 1, "t-train-x4": 4, "t-chat": 1, "t-docs": 1}

SEED = 2 ** 31 + 17   # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def lines(bench_copy):
    """One untraced and one traced run of every test cell, made once."""
    root, manifest = bench_copy
    out = {}
    for cell in TEST_CELLS:
        for traced in (0, 1):
            out[cell, traced] = harness.run_cell(root, manifest, cell, SEED, 0.5, traced,
                                                 require_tpu=False)
    return out


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell", list(TEST_CELLS))
def test_last_line_keeps_the_contract(lines, bench_copy, cell, traced):
    line = json.loads(json.dumps(lines[cell, traced]))      # it has to survive JSON
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == TEST_CELLS[cell]
    _, manifest = bench_copy
    loaded = harness.Cell(bench_copy[0], manifest, cell)
    allowed = loaded.per_layer if traced else loaded.end_to_end
    units = {m["name"]: m["unit"] for m in allowed}
    assert line["metrics"], "a line with no metric"
    for name, metric in line["metrics"].items():
        assert metric["unit"] == units[name] and isinstance(metric["value"], float)
    if not traced:
        assert set(line["metrics"]) == set(units)        # every end-to-end metric, setup_s too
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("cell", list(TEST_CELLS))
def test_no_device_metric_comes_from_a_cpu(lines, cell):
    """Idle share, kernel shares, MFU and peak memory need a chip: on the
    CPU their readers find nothing and the metrics are left out."""
    traced = lines[cell, 1]
    assert "busy_s" not in traced["device"] and "breakdown" not in traced
    assert traced["device"]["memory_peak_bytes"] is None
    for name in traced["metrics"]:
        assert not name.startswith(("device_idle", "flash_attn", "train_mfu", "train_peak",
                                    "zero3_collective"))


def test_train_cells_report_steps_and_serve_cells_requests(lines):
    assert "train_step_ms_p50" in lines["t-train", 1]["metrics"]
    assert "train_step_ms_p50" in lines["t-train-x4", 1]["metrics"]
    chat = lines["t-chat", 1]["metrics"]
    assert {"prefill_tick_pct", "decode_tick_ms_p50", "gen_late_p95_ms", "kv_live_pct_chat",
            "recompiles_in_window_chat"} <= set(chat)
    docs = lines["t-docs", 1]["metrics"]
    assert {"slot_occupancy_pct", "prefill_tick_ms_p50", "kv_live_pct_sat",
            "recompiles_in_window_sat", "backlog_left_pct_sat"} <= set(docs)
    assert 0 <= docs["backlog_left_pct_sat"]["value"] < 100
    assert 0 < docs["slot_occupancy_pct"]["value"] <= 100
    assert 0 < docs["kv_live_pct_sat"]["value"] <= 100


@pytest.mark.parametrize("given, want", [({"max_queue": 7}, 7), ({}, 1024)])
def test_the_server_takes_the_queue_limit_of_its_deployment(bench_copy, monkeypatch, given, want):
    """``serve.max_queue`` of the configuration reaches ``ServingConfig``; a
    configuration without the key keeps the server's default."""
    import jax

    import deepspeed_tpu.inference.serving as serving

    root, manifest = bench_copy
    cell = harness.Cell(root, manifest, "t-docs")
    assert "max_queue" not in cell.config["serve"]
    cell.config = dict(cell.config, serve=dict(cell.config["serve"], **given))
    built = {}

    class Scheduler:
        def __init__(self, engine, config, clock):
            built["config"] = config

    monkeypatch.setattr(serving, "ContinuousBatchingScheduler", Scheduler)
    env = harness.Env(SEED, 0.5, 0, None, jax.devices()[:1], None)
    cell.runner._server(cell, env, cell.family)
    assert built["config"].max_queue == want
    assert built["config"].slots == cell.config["serve"]["slots"]


@pytest.mark.parametrize("counters, want", [
    ({"queue_at_close": 105, "requests_scheduled": 512}, 100 * 105 / 512),
    ({"queue_at_close": 3680, "requests_scheduled": 4096}, 100 * 3680 / 4096),
    ({"queue_at_close": 0, "requests_scheduled": 512}, 0.0),      # ran dry: said, not left out
    ({"requests_scheduled": 512}, None), ({}, None)])
def test_backlog_left_reads_the_queue_at_the_close(counters, want):
    from benchmarks.lib import reducers

    spec = harness.load_json(harness.REPO_ROOT, "benchmarks", "layer_metrics",
                             "backlog_left_pct_sat.json")
    value = getattr(reducers, spec["reducer"])({"counters": counters}, **spec["args"])
    assert value == (pytest.approx(want) if want is not None else None)


def test_a_later_pr_adds_a_cell_a_mix_a_configuration_and_a_metric_as_files(bench_copy, tmp_path):
    """New files and new entries, no edit to a file that is there: a
    traffic mix (data), a configuration of the family (sizes), a per-layer
    metric (a reader naming a general reducer, and one with code of its
    own), and the cell that uses them."""
    import shutil
    root = str(tmp_path / "later_pr")
    shutil.copytree(bench_copy[0], root)
    before = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            before[path] = open(path, "rb").read()

    bench = os.path.join(root, "benchmarks")
    config = json.load(open(os.path.join(bench, "configs", "gpt2-test.json")))
    config.update(n_layer=3, n_embd=32, n_head=2)
    json.dump(config, open(os.path.join(bench, "configs", "gpt2-test-3l.json"), "w"))
    mix = json.load(open(os.path.join(bench, "traffic", "test-docs.json")))
    mix["prompt_len"] = {"dist": "uniform", "min": 40, "max": 40}
    json.dump(mix, open(os.path.join(bench, "traffic", "test-fixed.json"), "w"))
    json.dump({"reducer": "span_percentile", "args": {"span": "decode_tick_ms", "p": 99}},
              open(os.path.join(bench, "layer_metrics", "decode_tick_ms_p99.json"), "w"))
    with open(os.path.join(bench, "layer_metrics", "ticks_total.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    c = ctx['counters']\n"
                "    return c['prefill_ticks'] + c['decode_ticks']\n")
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    manifest["configs"].append({"name": "gpt2-test-3l", "source": "tests", "reduced": [],
                                "file": "benchmarks/configs/gpt2-test-3l.json", "why": "tests"})
    manifest["workloads"].append({"name": "t-new", "config": "gpt2-test-3l",
                                  "traffic": "test-fixed", "chips": 1, "why": "tests"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "serve_total_tok_s":
            metric["workloads"].append("t-new")
    layer = manifest["per_layer"][0]["layer"]
    for name, unit in (("decode_tick_ms_p99", "ms"), ("ticks_total", "count")):
        manifest["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                      "source": "program_span", "layer": layer,
                                      "moves": "serve_total_tok_s", "workloads": ["t-new"]})
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))

    untraced = harness.run_cell(root, manifest, "t-new", SEED, 0.5, 0, require_tpu=False)
    traced = harness.run_cell(root, manifest, "t-new", SEED, 0.5, 1, require_tpu=False)
    assert untraced["correct"] and set(untraced["metrics"]) == {"serve_total_tok_s", "setup_s"}
    # its own two, and the entries that list no cells, which every cell reports
    everywhere = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    assert set(traced["metrics"]) == {"decode_tick_ms_p99", "ticks_total"} | everywhere
    assert traced["metrics"]["ticks_total"]["value"] > 0
    for path, content in before.items():
        if not path.endswith("BENCHMARK.json"):
            assert open(path, "rb").read() == content, f"{path} was edited"


def test_the_command_refuses_to_run_without_a_tpu():
    """On this machine JAX has no TPU: the command exits non-zero and its
    standard output holds no result."""
    from envutil import cpu_subprocess_env
    run = subprocess.run(
        [sys.executable, os.path.join(harness.REPO_ROOT, "benchmarks", "run.py"), "--workload",
         "train-gpt2-medium-seq1k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=cpu_subprocess_env(), timeout=300)
    assert run.returncode != 0
    assert "no TPU" in run.stderr
    assert not [l for l in run.stdout.splitlines() if l.startswith("{") and '"metrics"' in l]


def test_an_unknown_workload_is_an_error(bench_copy):
    root, manifest = bench_copy
    with pytest.raises(harness.BenchmarkError):
        harness.run_cell(root, manifest, "no-such-cell", 1, 0.5, 0, require_tpu=False)
