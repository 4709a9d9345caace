"""Kernel-level attention autotuner: sweep, persist, reload.

Runs the real sweep machinery in interpret mode on CPU with tiny shapes —
the selection/persist path is identical to a chip window's, only the
numbers differ (attention_tuner module docstring)."""
import json
import os

import jax.numpy as jnp
import pytest

from deepspeed_tpu.autotuning.attention_tuner import (AttentionBlockTuner,
                                                      default_candidates)
from deepspeed_tpu.ops.pallas import attention_geometry as ag
from deepspeed_tpu.ops.pallas.attention_geometry import (AttentionGeometry,
                                                         resolve_geometry,
                                                         signature)


@pytest.fixture(autouse=True)
def _clean_geometry_state():
    yield
    ag.set_cache_path(None)


def test_sweep_persists_winner_and_kernel_reloads_it(tmp_path):
    results = tmp_path / "results"
    exps = tmp_path / "exps"
    cands = [
        AttentionGeometry(block_q=32, block_k=32, block_q_bwd=32,
                          block_k_bwd=32, tile=16, policy="lse"),
        AttentionGeometry(block_q=64, block_k=64, block_q_bwd=64,
                          block_k_bwd=64, tile=32, policy="recompute"),
    ]
    tuner = AttentionBlockTuner(results_dir=str(results), exps_dir=str(exps),
                                repeats=1, candidates=cands, interpret=True)
    best, records = tuner.tune(seq=64, head_dim=8, heads=1, batch=1,
                               causal=True, dtype=jnp.float32)
    assert best in cands
    assert all(r["status"] == "measured" for r in records), records

    # winners cache: the ds_config_optimal.json analog
    cache = results / "attention_blocks.json"
    assert cache.exists()
    sig = signature(64, 64, 8, 1, 1, True, jnp.dtype(jnp.float32))
    entry = json.load(cache.open())[sig]
    assert entry["geometry"] == best.as_dict()
    assert entry["seconds"] > 0 and entry["candidates"] == 2

    # per-experiment evidence trail
    exp = exps / f"attn_{sig}.json"
    assert exp.exists()
    assert len(json.load(exp.open())["records"]) == 2

    # the kernel's resolution layer must pick the banked winner up
    ag.set_cache_path(str(cache))
    geom, src = resolve_geometry(64, 64, 8, 1, 1, True, jnp.dtype(jnp.float32))
    assert src == "cache"
    assert all(getattr(geom, f) == getattr(best, f)
               for f in ("block_q", "block_k", "tile", "policy"))


def test_failed_candidates_prune_cleanly(tmp_path):
    bad = AttentionGeometry(block_q=48, block_k=48)  # does not tile 64...
    good = AttentionGeometry(block_q=32, block_k=32)
    tuner = AttentionBlockTuner(results_dir=str(tmp_path / "r"),
                                exps_dir=str(tmp_path / "e"),
                                repeats=1, candidates=[bad, good],
                                interpret=True)
    best, records = tuner.tune(seq=64, head_dim=8, causal=True,
                               dtype=jnp.float32)
    # ...but the geometry clamp makes it runnable, so either both measure
    # or the bad one records a failure — the sweep must survive regardless
    assert best is not None
    assert any(r["status"] == "measured" for r in records)
    assert os.path.exists(os.path.join(str(tmp_path / "r"),
                                       "attention_blocks.json"))


def test_default_sweep_is_staged(tmp_path):
    # no explicit candidates: stage 1 picks the forward pair forward-only,
    # stage 2 sweeps the backward axes on it — tens of programs, not the
    # full cross-product (chip-window compiles are the scarce resource)
    tuner = AttentionBlockTuner(results_dir=str(tmp_path / "r"),
                                exps_dir=str(tmp_path / "e"),
                                repeats=1, interpret=True)
    best, records = tuner.tune(seq=64, head_dim=8, causal=True,
                               dtype=jnp.float32)
    assert best is not None
    stages = [r["stage"] for r in records]
    assert set(stages) == {"fwd", "train"}
    from deepspeed_tpu.autotuning.attention_tuner import candidate_axes, tile_axis
    fwd_pairs, bwd_pairs = candidate_axes(64, 64, 8, True, itemsize=4)
    tiles = tile_axis(64, 64)
    assert stages.count("fwd") == len(fwd_pairs) * len(tiles)
    assert stages.count("train") == len(bwd_pairs) * len(tiles) * 2
    # the banked winner carries stage-2 (fwd+bwd) timing and full geometry
    assert (best.block_q_bwd, best.tile) != (None, None)
    # forward-only tune stops after stage 1
    tuner2 = AttentionBlockTuner(results_dir=str(tmp_path / "r2"),
                                 exps_dir=str(tmp_path / "e2"),
                                 repeats=1, interpret=True)
    _, rec2 = tuner2.tune(seq=64, head_dim=8, causal=True,
                          dtype=jnp.float32, train=False)
    assert all(r["stage"] == "fwd" for r in rec2)


def test_default_candidates_respect_divisibility_and_budget():
    cands = default_candidates(2048, 2048, 64, causal=True, itemsize=2)
    assert len(cands) > 4
    for c in cands:
        assert 2048 % c.block_q == 0 and 2048 % c.block_k == 0
        assert c.tile in (128, 256, 512) and c.policy in ("lse", "recompute")
    # the tile axis never exceeds the sequence
    assert all(c.tile == 256 for c in default_candidates(256, 256, 64, causal=False)
               if c.tile > 128)
    # tiny shapes degrade to the full-length block, never zero candidates
    tiny = default_candidates(64, 64, 8, causal=True)
    assert tiny and all(c.block_q == 64 for c in tiny)
