"""The kernel timer (tools/attn_tune.py) must run end-to-end on the CPU
backend at a tiny shape: a harness bug discovered during a chip run costs
chip time."""
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
