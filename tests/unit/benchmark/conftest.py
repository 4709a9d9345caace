"""A temporary copy of the benchmark with CPU-sized cells added to it: new
files and new entries only, as a later PR would add them."""

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# cell -> (config, traffic, chips, the real cell whose metrics it reports)
TEST_CELLS = {
    "t-train": ("gpt2-test", "test-train", 1, "train-gpt2-medium-seq1k"),
    "t-train-x4": ("gpt2-test-zero3", "test-train", 4, "train-gpt2-xl-zero3-x4"),
    "t-chat": ("gpt2-test", "test-chat", 1, "serve-gpt2-medium-chat"),
    "t-docs": ("gpt2-test", "test-docs", 1, "serve-gpt2-medium-docs-sat"),
}


def add_cell(manifest, name, config, traffic, chips, like):
    """Append a cell that reports what the cell ``like`` reports."""
    if config not in [c["name"] for c in manifest["configs"]]:
        manifest["configs"].append({"name": config, "source": "tests",
                                    "file": f"benchmarks/configs/{config}.json",
                                    "reduced": [], "why": "tests"})
    manifest["workloads"].append({"name": name, "config": config, "traffic": traffic,
                                  "chips": chips, "why": "tests"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if like in metric.get("workloads", ()):
            metric["workloads"].append(name)


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory):
    """(root, manifest) of a copy of the benchmark holding the test cells."""
    root = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copytree(os.path.join(REPO, "benchmarks"), os.path.join(root, "benchmarks"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, (config, traffic, chips, like) in TEST_CELLS.items():
        add_cell(manifest, name, config, traffic, chips, like)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root, manifest
