"""Perf-harness smoke tests: the perf tools (tools/perf_ladder,
tools/serve_bench) must run end-to-end on the CPU backend with tiny
models — a harness bug discovered during a chip run costs chip time."""
import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def _run_cpu(body, env_extra=None, timeout=420):
    sys.path.insert(0, REPO)
    from envutil import cpu_subprocess_env

    env = cpu_subprocess_env(n_virtual_devices=1)
    env.update(env_extra or {})
    p = subprocess.run([sys.executable, "-c", body], env=env, timeout=timeout,
                       capture_output=True, text=True, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(l) for l in p.stdout.splitlines()
            if l.strip().startswith("{")]


def test_perf_ladder_smoke_rungs_fused_and_offload():
    lines = _run_cpu(
        "import sys; sys.path.insert(0, 'tools');"
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "import perf_ladder; perf_ladder.main()",
        env_extra={"LADDER": "smoke,smoke_offload,smoke_bert,smoke_moe",
                   "LADDER_FUSED": "2"})
    tags = {l["tag"]: l for l in lines}
    assert {"smoke", "smoke_offload", "smoke_bert", "smoke_moe"} <= set(tags), tags
    for tag, row in tags.items():
        assert "error" not in row, row
        assert row["tokens_per_s"] > 0
        assert 0 < row["attn_flops_frac"] < 1
    assert "compile_s" in tags["smoke"]  # fused path reports compile time


def test_tune_bench_runs_end_to_end(tmp_path):
    lines = _run_cpu(
        "import sys; sys.path.insert(0, 'tools');"
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "import tune_bench; tune_bench.main()",
        env_extra={"TUNE_MODEL": "test", "TUNE_SEQ": "64",
                   "TUNE_MAX_MBS": "2", "TUNE_STAGES": "0",
                   "TUNE_STEPS": "2",
                   # keep the committed chip-measured artifacts out of reach
                   "TUNE_RESULTS_DIR": str(tmp_path / "results"),
                   "TUNE_EXPS_DIR": str(tmp_path / "exps")})
    row = lines[-1]
    assert row["winner"] is not None
    assert row["winner_measured_step_ms"] and row["winner_measured_step_ms"] > 0
    measured = [c for c in row["candidates"] if c["status"] == "measured"]
    assert measured, row


def test_attn_tune_runs_end_to_end(tmp_path):
    # block-geometry autotune sweep (tools/attn_tune.py) in interpret mode
    # against a tiny shape: a winner must be persisted to the redirected
    # results dir and reload through the kernel's geometry resolution
    lines = _run_cpu(
        "import sys; sys.path.insert(0, 'tools');"
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "import attn_tune; attn_tune.main()",
        env_extra={"ATTN_SHAPES": "64:8:1:1", "ATTN_REPEATS": "1",
                   "ATTN_DTYPE": "float32",
                   "ATTN_RESULTS_DIR": str(tmp_path / "results"),
                   "ATTN_EXPS_DIR": str(tmp_path / "exps")})
    row = lines[-1]
    assert "error" not in row, row
    assert row["winner"] is not None and row["measured"] > 0
    assert row["winner_ms"] and row["winner_ms"] > 0

    import json
    cache = tmp_path / "results" / "attention_blocks.json"
    assert cache.exists()
    (sig, entry), = json.load(cache.open()).items()
    assert entry["geometry"] == row["winner"]
    assert sig.startswith("q64_k64_d8_h1_b1_causal")

    # reload: the banked winner is what flash_attention would now run
    from deepspeed_tpu.ops.pallas import attention_geometry as ag
    try:
        ag.set_cache_path(str(cache))
        geom = ag.lookup_cached(sig)
        assert geom is not None and geom.as_dict() == row["winner"]
    finally:
        ag.set_cache_path(None)


def test_rlhf_bench_runs_end_to_end():
    lines = _run_cpu(
        "import sys; sys.path.insert(0, 'tools');"
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "import rlhf_bench; rlhf_bench.main()",
        env_extra={"RLHF_MODEL": "test", "RLHF_BATCH": "2",
                   "RLHF_PROMPT": "16", "RLHF_NEW": "8", "RLHF_ITERS": "2"})
    row = lines[-1]
    assert row["gen_tokens_per_s"] > 0
    assert row["rlhf_iters_per_s"] > 0
    # the hybrid engine actually alternated layouts
    assert row["hybrid_stats"].get("iters", 0) >= 2


def test_serve_bench_runs_end_to_end():
    """The PR-14 latency-under-load bench in a clean subprocess: Poisson
    arrivals through the continuous scheduler, TTFT/per-token/goodput row
    shape (the in-process both-modes comparison is covered by
    tests/unit/inference/test_serving.py::test_serve_bench_tool_smoke)."""
    lines = _run_cpu(
        "import sys; sys.path.insert(0, 'tools');"
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "import serve_bench; serve_bench.main()",
        env_extra={"SERVE_MODEL": "test", "SERVE_MODE": "continuous",
                   "SERVE_QPS": "50", "SERVE_REQUESTS": "4",
                   "SERVE_PROMPT": "16", "SERVE_NEW": "8",
                   "SERVE_SLOTS": "2", "SERVE_CHUNK": "8"})
    assert lines, "serve_bench printed no JSON"
    row = lines[-1]
    assert row["backend"] == "cpu"
    assert row["mode"] == "continuous" and row["finished"] == 4
    assert row["goodput_tok_s"] > 0
    assert row["ttft"]["p99"] >= row["ttft"]["p50"] > 0
    assert row["pool"]["used_blocks"] == 0
