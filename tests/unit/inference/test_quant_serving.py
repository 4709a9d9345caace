"""graft-quant-serve tier-1 gates: the quantized serving path end to end —
scheduler greedy parity (int8 weights + int8 KV vs fp) under the committed
logit envelope (``QUANT_PARITY_MAX_ABS``), the int8-KV-only parity +
identical pool counters, ``ServingConfig.weight_dtype``'s default, choices
and refusal edges, and the byte-budget pool sizing that turns int8 KV into
deeper admission."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (FINISHED,
                                             ContinuousBatchingScheduler,
                                             Request, ServingConfig)
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.ops.quantizer.weights import QUANT_PARITY_MAX_ABS, quantize_params
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology


@pytest.fixture(autouse=True)
def _clean():
    set_topology(None)
    yield
    set_topology(None)


def _fresh_engine(n_positions=128):
    cfg = get_gpt2_config("test", n_layer=2, n_positions=n_positions)
    icfg = DeepSpeedInferenceConfig(replace_with_kernel_inject=False)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    return InferenceEngine(GPT2LMHeadModel(cfg), icfg, topology=topo), cfg


@pytest.fixture(scope="module")
def engine_cfg():
    set_topology(None)
    engine, cfg = _fresh_engine()
    yield engine, cfg
    set_topology(None)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
            for p in lengths]


def _serve(engine, cfg, scfg, lengths=(5, 12, 9), max_new=6, seed=0):
    sched = ContinuousBatchingScheduler(engine, scfg)
    reqs = [Request(prompt=p, max_new_tokens=max_new)
            for p in _prompts(cfg, lengths, seed=seed)]
    for r in reqs:
        sched.submit(r)
    ticks = 0
    while any(not r.done for r in reqs):
        sched.step()
        ticks += 1
        assert ticks < 500, "starved"
    assert all(r.state == FINISHED for r in reqs)
    return sched, [r.output for r in reqs]


# ---------------------------------------------------------------------------
# the configuration alone decides the served weight dtype
# ---------------------------------------------------------------------------
def test_weight_dtype_layered_resolution(engine_cfg):
    """``ServingConfig.weight_dtype`` defaults to fp and takes the quantizer's
    choices; the scheduler serves what its configuration says, and a module
    built for a dtype the quantizer does not know refuses to trace."""
    engine, cfg = engine_cfg
    assert ServingConfig().weight_dtype == "fp"
    for wd in ("fp", "int8", "int4"):
        assert ServingConfig(weight_dtype=wd).weight_dtype == wd
    for bad in ("fp16", None):
        with pytest.raises(ValueError, match="weight_dtype"):
            ServingConfig(weight_dtype=bad)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(slots=4))
    assert sched.stats()["weight_dtype"] == "fp" and sched.module is engine.module
    module = GPT2LMHeadModel(dataclasses.replace(cfg, serve_weight_dtype="int2"))
    with pytest.raises(ValueError, match="weight_dtype"):
        jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_serving_config_validates_weight_dtype():
    with pytest.raises(ValueError):
        ServingConfig(weight_dtype="int2")
    scfg = ServingConfig()
    assert scfg.weight_dtype == "fp" and scfg.kv_quant is True
    assert scfg.weight_group_size == 64


# ---------------------------------------------------------------------------
# greedy parity + the committed logit envelope
# ---------------------------------------------------------------------------
def test_quantized_logit_parity_within_committed_envelope(engine_cfg):
    """Full-forward logits of the quantized module (int8/int4 codes +
    scales through the fused dequant GEMM) stay inside the COMMITTED
    envelope ``QUANT_PARITY_MAX_ABS`` vs the fp module — the serving
    equivalent of tools/parity_check.py's PARITY_MAX_ULP gate."""
    engine, cfg = engine_cfg
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    out = engine.module.apply({"params": engine.params}, ids)
    ref = out[0] if isinstance(out, (tuple, list)) else out
    for wd, envelope in QUANT_PARITY_MAX_ABS.items():
        qmodel = GPT2LMHeadModel(
            dataclasses.replace(cfg, serve_weight_dtype=wd))
        qp, qs = quantize_params(engine.params, wd, 64)
        qout = qmodel.apply({"params": qp, "quant": qs}, ids)
        ql = qout[0] if isinstance(qout, (tuple, list)) else qout
        delta = float(jnp.abs(ql - ref).max())
        assert delta <= envelope, (wd, delta, envelope)
        assert delta > 0  # really the quantized path, not fp passthrough


def test_int8_serving_greedy_token_parity(engine_cfg):
    """int8 weights + int8 KV (the serving default) greedy-match the fp
    scheduler AND offline ``engine.generate`` token-for-token on the
    tier-1 rig."""
    engine, cfg = engine_cfg
    lengths = (5, 12, 9)
    _, q_out = _serve(engine, cfg, ServingConfig(slots=4, weight_dtype="int8"),
                      lengths)
    _, fp_out = _serve(engine, cfg, ServingConfig(slots=4, kv_quant=False),
                       lengths)
    assert q_out == fp_out
    for p, o in zip(_prompts(cfg, lengths), q_out):
        ref = np.asarray(engine.generate(p[None, :], max_new_tokens=6))
        assert o == list(ref[0, len(p):])


def test_kv_quant_only_parity_and_identical_counters(engine_cfg):
    """int8 KV with fp weights under the continuous scheduler: greedy
    outputs match the fp-KV run and the block-pool counters are
    IDENTICAL — quantization changes bytes per block, never the
    allocator's token accounting."""
    engine, cfg = engine_cfg
    qsched, q_out = _serve(engine, cfg, ServingConfig(slots=4, kv_quant=True))
    fsched, f_out = _serve(engine, cfg, ServingConfig(slots=4, kv_quant=False))
    assert q_out == f_out
    qc, fc = qsched.pool.counters(), fsched.pool.counters()
    assert qc == fc
    # ...but the bytes-per-block evidence differs: int8 KV packs strictly
    # more blocks into a GB than the fp pool
    qs, fs = qsched.stats()["pool"], fsched.stats()["pool"]
    assert qs["kv_block_bytes"] < fs["kv_block_bytes"]
    assert qs["kv_blocks_per_gb"] > fs["kv_blocks_per_gb"]


def test_int4_serving_runs_and_stays_plausible(engine_cfg):
    """int4 is lossy — no token-parity claim — but the quantized drafter
    path must run to completion and emit full-length outputs."""
    engine, cfg = engine_cfg
    sched, outs = _serve(engine, cfg, ServingConfig(slots=4, weight_dtype="int4"))
    assert all(len(o) == 6 for o in outs)
    assert sched.stats()["weight_dtype"] == "int4"


# ---------------------------------------------------------------------------
# speculation: quantized drafter under a quantized target
# ---------------------------------------------------------------------------
def test_speculative_quantized_drafter_lossless(engine_cfg):
    """Speculation with an int8 target quantizes the drafter too (int8,
    always) and stays LOSSLESS: greedy outputs equal the non-speculative
    quantized run, and draft acceptance is recorded."""
    engine, cfg = engine_cfg
    d_cfg = get_gpt2_config("test", n_layer=1, n_positions=128)
    d_model = GPT2LMHeadModel(d_cfg)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    from flax.linen import meta
    d_params = meta.unbox(d_model.init(jax.random.PRNGKey(1), ids)["params"])

    base = dict(slots=4, weight_dtype="int8")
    _, plain = _serve(engine, cfg, ServingConfig(**base))
    scfg = ServingConfig(**base, speculation={"enabled": True, "k": 3})
    sched = ContinuousBatchingScheduler(engine, scfg, drafter=(d_model, d_params))
    reqs = [Request(prompt=p, max_new_tokens=6)
            for p in _prompts(cfg, (5, 12, 9))]
    for r in reqs:
        sched.submit(r)
    ticks = 0
    while any(not r.done for r in reqs):
        sched.step()
        ticks += 1
        assert ticks < 500
    assert [r.output for r in reqs] == plain
    st = sched.stats()
    assert st["drafted"] > 0 and 0.0 <= st["acceptance_rate"] <= 1.0


# ---------------------------------------------------------------------------
# byte-budget pool sizing: int8 KV admits deeper on the same HBM
# ---------------------------------------------------------------------------
def test_kv_pool_bytes_admits_deeper_when_quantized(engine_cfg):
    """The SAME byte budget sizes strictly more KV blocks under int8 KV
    than under fp KV."""
    engine, cfg = engine_cfg
    budget = 64 * 1024
    q = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=4, kv_quant=True, kv_pool_bytes=budget))
    f = ContinuousBatchingScheduler(
        engine, ServingConfig(slots=4, kv_quant=False, kv_pool_bytes=budget))
    assert q.pool.num_blocks > f.pool.num_blocks
    # measured per-token footprints honor the budget
    assert q.pool.num_blocks * q.pool.block_size * q._kv_bytes_per_token() <= budget
    assert f.pool.num_blocks * f.pool.block_size * f._kv_bytes_per_token() <= budget


# ---------------------------------------------------------------------------
# refusal edges
# ---------------------------------------------------------------------------
def test_double_quantization_refused(engine_cfg):
    """An engine already serving its own int8 weight view must refuse
    serving.weight_dtype rather than quantize codes twice."""
    engine, cfg = engine_cfg
    engine._wq_scales = object()
    try:
        with pytest.raises(ValueError, match="double-quantize"):
            ContinuousBatchingScheduler(
                engine, ServingConfig(slots=4, weight_dtype="int8"))
    finally:
        engine._wq_scales = None


def test_module_without_seam_refused(engine_cfg):
    """A model family without the serve_weight_dtype seam is refused
    loudly (never silently served fp)."""
    from deepspeed_tpu.inference.serving.scheduler import _quant_view

    class NoSeam:
        config = None

    with pytest.raises(NotImplementedError, match="serve_weight_dtype"):
        _quant_view(NoSeam(), {}, "int8", 64)
