"""Sequence-parallel attention parity: ring + Ulysses vs the unsharded XLA
reference, forward and backward, on an 8-virtual-device CPU mesh.

Mirrors the reference's kernel-parity test style (SURVEY §4: jnp reference
vs kernel) — here the "kernel" is a distributed algorithm.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import xla_attention
from deepspeed_tpu.parallel.ring_attention import DistributedAttention, ring_attention, ulysses_attention
from deepspeed_tpu.parallel.topology import MeshTopology


def _qkv(b=2, l=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture
def seq4_mesh():
    return MeshTopology(sequence=4, data=2).mesh


@pytest.fixture
def seq2_tp2_mesh():
    return MeshTopology(sequence=2, tensor=2, data=2).mesh


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_xla(seq4_mesh, causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, causal=causal, mesh=seq4_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_xla(seq4_mesh, causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, causal=causal, mesh=seq4_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ring_with_tensor_parallel_heads(seq2_tp2_mesh):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, causal=True, mesh=seq2_tp2_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ulysses_with_tensor_parallel_heads(seq2_tp2_mesh):
    # h=4, tp=2 → 2 local heads, sp=2 → 1 head after scatter: exactly divisible
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, causal=True, mesh=seq2_tp2_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_gradients_match_xla(seq4_mesh, impl):
    q, k, v = _qkv()
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]

    def loss_sp(q, k, v):
        return jnp.sum(fn(q, k, v, causal=True, mesh=seq4_mesh)**2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True)**2)

    # jitted: eagerly, the backward of a shard_map on 8 devices is traced and
    # dispatched an operation at a time, and the values compared are the same
    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_ring_under_jit(seq4_mesh):
    q, k, v = _qkv()
    jitted = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True, mesh=seq4_mesh))
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(jitted(q, k, v)), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_distributed_attention_wrapper(seq4_mesh):
    q, k, v = _qkv()
    attn = DistributedAttention(xla_attention, mesh=seq4_mesh)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(attn(q, k, v, causal=True)), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_ring_in_model_end_to_end():
    """GPT-2 with attention_backend='ring' trains one step on a sequence-
    sharded mesh and matches the xla-backend loss."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    losses = {}
    for backend, topo in [("xla", MeshTopology(data=8)),
                          ("ring", MeshTopology(sequence=4, data=2))]:
        cfg = get_gpt2_config("test", n_positions=64, attention_backend=backend)
        model = GPT2LMHeadModel(cfg)
        ds_config = {
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config, topology=topo)
        batch = {"input_ids": np.tile(np.arange(64, dtype=np.int32) % 250, (8, 1))}
        losses[backend] = float(engine.train_batch(batch))
        set_topology(None)
    assert np.isfinite(losses["ring"])
    np.testing.assert_allclose(losses["ring"], losses["xla"], atol=1e-4, rtol=1e-4)


def test_ulysses_flash_local_backend(seq4_mesh):
    """Ulysses with the Pallas flash kernel as the LOCAL attention op —
    the production TPU composition (all-to-all reshard + flash inner)."""
    rng = np.random.default_rng(7)
    mk = lambda: jnp.asarray(rng.standard_normal((2, 128, 4, 16)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    ref = xla_attention(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, causal=True, local_backend="flash",
                            mesh=seq4_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)
