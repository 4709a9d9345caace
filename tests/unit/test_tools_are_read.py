"""What earns a place beside the benchmark: a script under ``tools/`` is one
that something still reads, and the repository measures itself in one place.

A script that has given its answer leaves the answer in ``PERF.md`` and
goes. What stays is named by a test, by a file of the benchmark, or by the
table of scripts in ``README.md``, so that a reader who finds it can tell who
needs it.
"""
import ast
import json
import os
import py_compile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOOLS = sorted(name for name in os.listdir(os.path.join(REPO, "tools")) if name.endswith(".py"))
SKIPPED_DIRS = {"chiprun_out", "_parent", "_committed_copy"}


def _files(top, suffixes):
    for folder, dirs, names in os.walk(os.path.join(REPO, top)):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in SKIPPED_DIRS]
        for name in names:
            if name.endswith(suffixes):
                yield os.path.join(folder, name)


def _read(path):
    with open(path, encoding="utf-8") as fh:
        return fh.read()


@pytest.fixture(scope="module")
def readers():
    """Every text that may name a script: the test files but this one, the
    benchmark's files, and the rows of the README's table of scripts."""
    texts = [_read(p) for p in _files("tests", (".py",)) if p != os.path.abspath(__file__)]
    texts += [_read(p) for p in _files("benchmarks", (".py", ".json", ".md"))]
    texts += [line for line in _read(os.path.join(REPO, "README.md")).splitlines()
              if line.startswith("| `")]
    return texts


@pytest.mark.parametrize("script", TOOLS)
def test_a_script_that_stays_compiles_and_is_named_by_what_reads_it(script, readers, tmp_path):
    py_compile.compile(os.path.join(REPO, "tools", script), cfile=str(tmp_path / "out.pyc"),
                       doraise=True)
    stem = script[:-len(".py")]
    assert any(stem in text for text in readers), (
        f"no test, no file under benchmarks/ and no row of README.md's table of scripts "
        f"names tools/{script}: leave its answer in PERF.md and delete it")


def test_the_readmes_table_lists_the_scripts_there_are():
    """A row of the README's table names no script that is gone, and every
    script has a row: the table is where a reader learns what each answers."""
    rows = [line for line in _read(os.path.join(REPO, "README.md")).splitlines()
            if line.startswith("| `") and ".py`" in line.split("|")[1]]
    listed = sorted(name.strip(" `") for row in rows for name in row.split("|")[1].split(","))
    assert listed == TOOLS


def test_there_is_one_benchmark():
    """``BENCHMARK.json``'s command is the one program that prints a line of
    metrics: no script at the root or under ``tools/`` holds, as a string of
    its own, an end-to-end metric's name or a key of the one-line contract
    the old root benchmark printed; and that benchmark's yardstick, the
    reference's 2020 headline, is in none of the tree's Python."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    programs = [arg for arg in manifest["command"] if arg.endswith(".py")]
    assert programs == ["benchmarks/run.py"] and os.path.exists(os.path.join(REPO, programs[0]))
    headline, ratio = "BASELINE_" + "TFLOPS", "vs_" + "baseline"
    keys = {m["name"] for m in manifest["end_to_end"]} | {"metric", ratio}
    scripts = [os.path.join(REPO, n) for n in os.listdir(REPO) if n.endswith(".py")]
    scripts += [os.path.join(REPO, "tools", n) for n in TOOLS]
    for path in scripts:
        strings = {node.value for node in ast.walk(ast.parse(_read(path)))
                   if isinstance(node, ast.Constant) and isinstance(node.value, str)}
        assert not keys & strings, (os.path.relpath(path, REPO), sorted(keys & strings))
    for path in _files(".", (".py",)):
        text = _read(path)
        assert headline not in text and ratio not in text, os.path.relpath(path, REPO)
