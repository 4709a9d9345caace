"""``BENCHMARK.json`` against the contract it is written to, as far as a
test can hold it: keys, names, units, files, and that every per-layer
metric's ``moves`` is reported by each cell the metric is reported in."""

import ast
import json
import os
import re
import shutil

import pytest

from benchmarks.lib.harness import REPO_ROOT, Cell, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a key ``reduced`` may never name: a width, or the experts a token takes. ``hidden_size``, not
# ``hidden``: ``num_hidden_layers`` is a depth, and every configuration that cuts it names it
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|_dim$|_rank$|head_size|n_embd|expand"
                   r"|experts_per_tok)")


def manifest():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


M = manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO_ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(one_line(w) for w in M["command"])
    assert 1 <= len(M["paths"]) <= 16
    assert 1 <= len(M["workloads"]) <= 24 and 1 <= len(M["configs"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128


def test_full_check_fits_its_allowance_at_24_cells():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_command_names_only_files_under_paths():
    for word in M["command"][1:]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(REPO_ROOT, word)):
            assert any(word.startswith(p + "/") for p in M["paths"])


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    with open(os.path.join(REPO_ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    assert len(config["reduced"]) <= 16
    assert not [k for k in config["reduced"] if WIDTH.search(k)]
    assert any(w["config"] == config["name"] for w in M["workloads"])
    assert os.path.exists(os.path.join(REPO_ROOT, "benchmarks", "reference", body["family"] + ".py"))
    assert os.path.exists(os.path.join(REPO_ROOT, "benchmarks", "families", body["family"] + ".py"))


@pytest.mark.parametrize("reduced, refused", [
    (["num_hidden_layers", "n_routed_experts", "vocab_size", "layer_types"], []),      # depth and counts
    (["hybrid_override_pattern", "rope_layout", "sliding_window_layout", "moe_num_primary_experts",
      "attn_pdrop"], []),
    (["num_hidden_layers", "hidden_size"], ["hidden_size"]),
    (["moe_ffn_hidden_size", "intermediate_size", "moe_intermediate_size"],
     ["moe_ffn_hidden_size", "intermediate_size", "moe_intermediate_size"]),
    (["q_lora_rank", "kv_lora_rank", "head_dim", "qk_rope_head_dim", "v_head_dim"],
     ["q_lora_rank", "kv_lora_rank", "head_dim", "qk_rope_head_dim", "v_head_dim"]),
    (["moe_latent_size", "ssm_state_size", "n_embd", "expand", "num_experts_per_tok", "vocab_size"],
     ["moe_latent_size", "ssm_state_size", "n_embd", "expand", "num_experts_per_tok"]),
], ids=["depth", "layouts", "hidden", "expert-widths", "ranks-and-heads", "states-and-experts-a-token"])
def test_a_made_up_reduced_may_cut_depth_and_no_width(reduced, refused):
    assert [k for k in reduced if WIDTH.search(k)] == refused


def test_configs_are_the_published_sizes():
    sizes = {c["name"]: json.load(open(os.path.join(REPO_ROOT, c["file"]))) for c in M["configs"]}
    want = {"gpt2-medium": (1024, 24, 16), "gpt2-xl": (1600, 48, 25)}
    for name, (n_embd, n_layer, n_head) in want.items():
        body = sizes[name]
        assert (body["n_embd"], body["n_layer"], body["n_head"]) == (n_embd, n_layer, n_head)
        assert body["vocab_size"] == 50257 and body["n_positions"] == 1024


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and one_line(cell["why"])
    assert cell["chips"] in (1, 4)
    assert cell["config"] in [c["name"] for c in M["configs"]]
    loaded = Cell(REPO_ROOT, M, cell["name"])
    assert os.path.exists(os.path.join(REPO_ROOT, "benchmarks", "runners",
                                       loaded.traffic["kind"] + ".py"))
    names = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and loaded.per_layer


def test_cells_are_distinct_and_few_take_four_chips():
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in M["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert one_line(metric["layer"])
        base = os.path.join(REPO_ROOT, "benchmarks", "layer_metrics", metric["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_metric_names_are_distinct_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0] and setup[0]["bound"] <= 0.1


# the runner a cell's traffic names: train or serve
KIND = {w["name"]: load_json(REPO_ROOT, "benchmarks", "traffic", w["traffic"] + ".json")["kind"]
        for w in M["workloads"]}
LISTED = [(m, cell) for m in M["per_layer"] for cell in m.get("workloads", CELLS)]


@pytest.mark.parametrize("metric, cell", LISTED, ids=lambda v: v if isinstance(v, str) else v["name"])
def test_moves_is_reported_in_each_cell_the_metric_lists(metric, cell):
    """An entry lists the cells whose runs its reader finds something in, whichever PR
    brought each: every one is a cell, reports the metric the entry moves, and a reader
    of serving ticks lists no training cell (nor one of steps a serving cell). An entry
    that lists none is read in every cell, a later PR's too."""
    moved = [m for m in M["end_to_end"] if m["name"] == metric["moves"]]
    assert moved, f"{metric['name']} moves no end-to-end metric"
    assert cell in CELLS and cell in moved[0].get("workloads", CELLS)
    listed = metric.get("workloads", CELLS)
    if metric["moves"] != "setup_s":
        assert {KIND[c] for c in listed} == {KIND[cell]}
    assert listed == [c for c in CELLS if c in listed]           # once each, in the cells' order


def reader_body(name, root=REPO_ROOT):
    """What a per-layer entry's reader does, apart from what it is called and what it
    says of itself: a ``.json`` by its content, a ``.py`` with the docstring set aside."""
    base = os.path.join(root, "benchmarks", "layer_metrics", name)
    if os.path.exists(base + ".py"):
        with open(base + ".py") as f:
            tree = ast.parse(f.read())
        if ast.get_docstring(tree, clean=False) is not None:
            tree.body = tree.body[1:]
        return "py:" + ast.dump(tree)
    with open(base + ".json") as f:
        return "json:" + json.dumps(json.load(f), sort_keys=True)


def copies(per_layer, root=REPO_ROOT):
    """Pairs of entries that move one end-to-end metric through readers with equal bodies."""
    first, found = {}, []
    for metric in per_layer:
        key = (metric["moves"], reader_body(metric["name"], root))
        if key in first:
            found.append((first[key], metric["name"]))
        first.setdefault(key, metric["name"])
    return found


def test_no_two_entries_that_move_one_metric_share_a_readers_body():
    """A cell that needs an accepted reader joins that entry's ``workloads``; it does not
    bring the reader again under a name of its own."""
    assert copies(M["per_layer"]) == [], "add the cell to the first entry's workloads instead"


def test_a_copy_under_another_name_is_found(tmp_path):
    folder = tmp_path / "benchmarks" / "layer_metrics"
    shutil.copytree(os.path.join(REPO_ROOT, "benchmarks", "layer_metrics"), folder)
    shutil.copy(folder / "device_idle_pct_sat.json", folder / "device_idle_pct_next.json")
    source = (folder / "tick_ahead_pct_sat.py").read_text()
    (folder / "tick_ahead_pct_next.py").write_text(
        '"""The same reader, said otherwise."""' + source[source.index('"""', 3) + 3:])
    entry = {m["name"]: m for m in M["per_layer"]}
    added = [dict(entry["device_idle_pct_sat"], name="device_idle_pct_next", workloads=CELLS[-1:]),
             dict(entry["tick_ahead_pct_sat"], name="tick_ahead_pct_next", workloads=CELLS[-1:])]
    assert copies(M["per_layer"] + added, str(tmp_path)) == [
        ("device_idle_pct_sat", "device_idle_pct_next"), ("tick_ahead_pct_sat", "tick_ahead_pct_next")]
    # the same body under another end-to-end metric is no copy: an entry has one ``moves``
    assert reader_body("device_idle_pct_chat") == reader_body("device_idle_pct_sat")
    assert entry["device_idle_pct_chat"]["moves"] != entry["device_idle_pct_sat"]["moves"]


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in M["per_layer"]}
    perf = open(os.path.join(REPO_ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers does not name {layer!r}"


def test_files_under_paths_are_named_from_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in M["paths"]:
        assert allowed.match(path) and len(path) <= 200
        for folder, dirs, files in os.walk(os.path.join(REPO_ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), REPO_ROOT)
                assert allowed.match(rel), rel
