"""Tunable-geometry flash attention: parity across block geometries and
backward policies, and the layered geometry resolution itself.

The kernel's work partitioning is now a knob (ISSUE 5 / FlashAttention-2:
the partitioning is where the last 1.5-2x lives), so every geometry the
autotuner may pick must be bit-compatible with the reference — interpret
mode runs the same Pallas code path on CPU as the chip runs compiled.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import attention_geometry as ag
from deepspeed_tpu.ops.pallas.attention_geometry import (AttentionGeometry,
                                                         parse_spec,
                                                         resolve_geometry,
                                                         signature,
                                                         store_winner)
from deepspeed_tpu.ops.transformer.attention import dot_product_attention


@pytest.fixture(autouse=True)
def _clean_geometry_state(tmp_path):
    """Every test sees an empty winners cache: it points into tmp so repo
    artifacts can't leak in."""
    ag.set_cache_path(str(tmp_path / "attention_blocks.json"))
    yield
    ag.set_cache_path(None)


def _rand_qkv(seed, b, l, h, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    return q, k, v


# geometry x policy grid: asymmetric fwd/bwd blocks, compute tiles of a
# block's size and below it, both recompute policies (>= 6 combos per the acceptance
# criteria; every one must match the XLA reference in fwd AND grads)
GEOMETRIES = [
    dict(block_q=64, block_k=64, block_q_bwd=64, block_k_bwd=64,
         tile=64, policy="lse"),
    dict(block_q=64, block_k=128, block_q_bwd=32, block_k_bwd=64,
         tile=32, policy="lse"),
    dict(block_q=128, block_k=64, block_q_bwd=64, block_k_bwd=32,
         tile=16, policy="lse"),
    dict(block_q=64, block_k=64, block_q_bwd=64, block_k_bwd=64,
         tile=32, policy="recompute"),
    dict(block_q=128, block_k=128, block_q_bwd=32, block_k_bwd=32,
         tile=64, policy="recompute"),
    dict(block_q=32, block_k=64, block_q_bwd=128, block_k_bwd=64,
         tile=16, policy="recompute"),
]


def _loss(fn):
    def wrapped(q, k, v):
        o = fn(q, k, v)
        return (o * jnp.sin(jnp.arange(o.size).reshape(o.shape))).sum()
    return wrapped


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("geom", GEOMETRIES,
                         ids=[AttentionGeometry(**g).spec() for g in GEOMETRIES])
def test_geometry_policy_parity_fwd_and_grads(geom, causal):
    q, k, v = _rand_qkv(0, 1, 128, 2, 32)
    ref_fn = _loss(lambda q, k, v: dot_product_attention(
        q, k, v, backend="xla", causal=causal))
    fl_fn = _loss(lambda q, k, v: dot_product_attention(
        q, k, v, backend="flash", causal=causal, **geom))
    ref_o = dot_product_attention(q, k, v, backend="xla", causal=causal)
    fl_o = dot_product_attention(q, k, v, backend="flash", causal=causal, **geom)
    np.testing.assert_allclose(np.asarray(fl_o), np.asarray(ref_o),
                               atol=2e-5, rtol=2e-5)
    ref_g = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
    fl_g = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
    for rg, fg, name in zip(ref_g, fl_g, "qkv"):
        np.testing.assert_allclose(np.asarray(fg), np.asarray(rg),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch for {geom}")


@pytest.mark.parametrize("tile", [32, 16])
def test_kv_lengths_parity_across_tiles(tile):
    # the masked (right-padded) path drives the walk's bounds hardest: they
    # are run-time scalars, and dead K tiles must contribute exactly zero
    # whether a block is one tile or several
    q, k, v = _rand_qkv(3, 2, 128, 2, 32)
    kv_lengths = jnp.array([96, 40], jnp.int32)
    ref_fn = _loss(lambda q, k, v: dot_product_attention(
        q, k, v, backend="xla", causal=True, kv_lengths=kv_lengths))
    fl_fn = _loss(lambda q, k, v: dot_product_attention(
        q, k, v, backend="flash", causal=True, kv_lengths=kv_lengths,
        block_q=32, block_k=32, tile=tile, policy="recompute"))
    ref_g = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
    fl_g = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
    for rg, fg, name in zip(ref_g, fl_g, "qkv"):
        np.testing.assert_allclose(np.asarray(fg), np.asarray(rg),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch (tile={tile})")


def test_recompute_policy_stashes_no_lse_residual():
    # policy="recompute" must drop the [B,H,L] log-sum-exp from the
    # fwd->bwd residuals (that HBM saving is the policy's whole point)
    from deepspeed_tpu.ops.pallas.flash_attention import _flash_attention_bhld_fwd
    q, k, v = _rand_qkv(4, 1, 64, 1, 32)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    common = (None, 32**-0.5, True, 32, 32, 32, 32, 32)
    _, res_lse = _flash_attention_bhld_fwd(qt, kt, vt, *common, "lse", True, None)
    _, res_rec = _flash_attention_bhld_fwd(qt, kt, vt, *common, "recompute", True, None)
    assert res_lse[4] is not None and res_lse[4].shape == (1, 1, 64)
    assert res_rec[4] is None


# ---------------------------------------------------------------------------
# spec grammar + resolution layering
# ---------------------------------------------------------------------------
def test_parse_spec_grammar():
    g = parse_spec("block_q=512,block_k=1024,tile=256,policy=recompute")
    assert (g.block_q, g.block_k, g.tile, g.policy) == (512, 1024, 256, "recompute")
    assert parse_spec("512,1024") == AttentionGeometry(block_q=512, block_k=1024)
    assert parse_spec("256") == AttentionGeometry(block_q=256, block_k=256)
    assert parse_spec("") == AttentionGeometry()
    assert parse_spec(g.spec()) == g  # spec() round-trips
    with pytest.raises(ValueError):
        parse_spec("block_q=512,oops=1")
    with pytest.raises(ValueError):
        parse_spec("policy=sometimes")
    with pytest.raises(ValueError):
        parse_spec("block_q=-8")


def test_default_geometry_shape_keyed():
    """The v5e's winners (PERF.md section 6, PR 29), as code."""
    for heads, batch in ((16, 8), (25, 4)):  # the two training cells' call shapes
        cell, src = resolve_geometry(1024, 1024, 64, heads, batch, True)
        assert src == "default"
        assert cell == AttentionGeometry(block_q=1024, block_k=1024, block_q_bwd=1024,
                                         block_k_bwd=1024, tile=512, policy="lse")
    lng, _ = resolve_geometry(8192, 8192, 64, 16, 1, True)
    assert (lng.block_q, lng.block_k) == (2048, 2048)  # the forward streams larger blocks
    assert (lng.block_q_bwd, lng.block_k_bwd, lng.tile) == (1024, 1024, 512)
    wide, _ = resolve_geometry(8192, 8192, 128, 16, 1, True)
    assert (wide.block_q, wide.block_k) == (1024, 1024)  # wide heads: half the rows a block


@pytest.mark.parametrize("length", [1000, 8192])
def test_geometry_tuned_at_1024_clamps_elsewhere(length):
    """A pin tuned at 1,024 positions still tiles 1,000 and 8,192: blocks
    clamp to divisors of the sequence, the compute tile to divisors of the
    blocks, and the kernel runs."""
    from deepspeed_tpu.ops.pallas.flash_attention import _tiles
    pin = parse_spec("block_q=1024,block_k=1024,block_q_bwd=1024,block_k_bwd=1024,tile=512")
    g, src = resolve_geometry(length, length, 64, 16, 1, True, overrides=pin)
    assert src == "explicit"
    for blk in (g.block_q, g.block_k, g.block_q_bwd, g.block_k_bwd):
        assert length % blk == 0 and blk <= 1024
    for tile in _tiles(g.block_q, g.block_k, g.tile) + _tiles(g.block_q_bwd, g.block_k_bwd, g.tile):
        assert tile <= 512 and g.block_q % tile == 0 and g.block_k % tile == 0
    if length == 1000:  # small enough to run here: 8 x 125, the largest chain block that divides
        q, k, v = _rand_qkv(11, 1, length, 1, 16)
        ref = dot_product_attention(q, k, v, backend="xla", causal=True)
        out = dot_product_attention(q, k, v, backend="flash", causal=True,
                                    geometry_spec=pin.spec())
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_resolution_precedence_env_config_cache_default():
    """explicit > winners file > shape default, field by field."""
    shape = dict(lq=256, lk=256, head_dim=32, heads=2, batch=1, causal=True)
    sig = signature(256, 256, 32, 2, 1, True)

    g, src = resolve_geometry(**shape)
    assert src == "default"
    default = g

    store_winner(sig, AttentionGeometry(block_q=64, block_k=128))
    g, src = resolve_geometry(**shape)
    assert (src, g.block_q, g.block_k) == ("cache", 64, 128)
    assert g.policy == default.policy  # unset cache fields fall through to the default

    g, src = resolve_geometry(**shape,
                              overrides=AttentionGeometry(block_q=16, policy="recompute"))
    assert (src, g.block_q, g.policy) == ("explicit", 16, "recompute")
    assert g.block_k == 128  # the winners file still supplies unset fields


def test_cache_winner_clamped_to_divisors():
    # a winner tuned at 8k (block 1024) must not break a smaller call
    sig = signature(128, 128, 32, 2, 1, True)
    store_winner(sig, AttentionGeometry(block_q=1024, block_k=768))
    g, src = resolve_geometry(128, 128, 32, 2, 1, True)
    assert src == "cache"
    assert 128 % g.block_q == 0 and 128 % g.block_k == 0


def test_forward_only_override_keeps_shape_default_bwd():
    # overriding just the forward tiling must not disturb the backward's
    # shape-keyed defaults (the two passes prefer different partitionings)
    g, _ = resolve_geometry(256, 256, 32, 2, 1, True,
                            overrides=parse_spec("block_q=64,block_k=32"))
    assert (g.block_q, g.block_k) == (64, 32)
    base = ag.default_geometry(256, 256, 32, True)
    assert (g.block_q_bwd, g.block_k_bwd) == (base.block_q_bwd, base.block_k_bwd)
    assert (g.tile, g.policy) == (base.tile, "lse")


def test_store_and_reload_winner_roundtrip(tmp_path):
    path = str(tmp_path / "winners.json")
    sig = signature(512, 512, 64, 4, 2, False, jnp.dtype(jnp.bfloat16))
    geom = AttentionGeometry(block_q=128, block_k=256, tile=128,
                             policy="recompute")
    store_winner(sig, geom, path=path, seconds=0.012, backend="cpu")
    with open(path) as f:
        data = json.load(f)
    assert data[sig]["geometry"] == geom.as_dict()
    assert data[sig]["seconds"] == 0.012
    assert ag.lookup_cached(sig, path=path) == geom
    # corrupt entries degrade to None, not an exception
    data[sig]["geometry"] = {"block_q": "huge"}
    with open(path, "w") as f:
        json.dump(data, f)
    assert ag.lookup_cached(sig, path=path) is None


def test_attention_config_block_installs_engine_default():
    """The engine's "attention" block reaches the kernel through the model
    configuration: merged over the model's own ``attention_blocks`` spec on
    a copy of the module, which hands it down as ``geometry_spec``."""
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.models.common import attention_geometry_kwargs
    from deepspeed_tpu.runtime.config import AttentionConfig, DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import _apply_program_knobs

    cfg = AttentionConfig(block_q=256, policy="recompute")
    assert cfg.geometry_fields() == {"block_q": 256, "policy": "recompute"}

    model = GPT2LMHeadModel(get_gpt2_config(
        "test", attention_backend="flash", attention_blocks="block_q=64,block_k=128"))
    ds = DeepSpeedConfig({"train_batch_size": 8,
                          "attention": {"block_q": 256, "policy": "recompute"}},
                         dp_world_size=1)
    built = _apply_program_knobs(model, ds)
    assert model.config.attention_blocks == "block_q=64,block_k=128"
    spec = attention_geometry_kwargs(built.config)["geometry_spec"]
    assert parse_spec(spec) == AttentionGeometry(block_q=256, block_k=128,
                                                 policy="recompute")
    g, src = resolve_geometry(512, 512, 64, 4, 1, True, overrides=parse_spec(spec))
    assert (src, g.block_q, g.block_k, g.policy) == ("explicit", 256, 128, "recompute")
    # and it leaves nothing behind for a call that names no geometry
    assert resolve_geometry(512, 512, 64, 4, 1, True)[1] == "default"


def test_model_config_spec_overrides_resolution():
    # models pass cfg.attention_blocks through attention_geometry_kwargs as
    # a geometry_spec — highest precedence, but CLAMPED per call shape
    from deepspeed_tpu.models.common import attention_geometry_kwargs

    class Cfg:
        attention_backend = "flash"
        attention_blocks = "block_q=32,block_k=64,policy=recompute"

    kw = attention_geometry_kwargs(Cfg())
    assert kw == {"geometry_spec": Cfg.attention_blocks}

    class XlaCfg:
        attention_backend = "xla"
        attention_blocks = "block_q=32"

    assert attention_geometry_kwargs(XlaCfg()) == {}  # xla takes no blocks

    q, k, v = _rand_qkv(7, 1, 128, 2, 32)
    ref = dot_product_attention(q, k, v, backend="xla", causal=True)
    out = dot_product_attention(q, k, v, backend="flash", causal=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_model_spec_clamps_but_explicit_blocks_fall_back():
    # a per-model pin tuned at one shape must stay on the kernel at shapes
    # its blocks don't divide (clamped); the same sizes as direct kwargs
    # keep the historical warn-and-fallback-to-XLA contract
    q, k, v = _rand_qkv(9, 1, 96, 2, 32)  # 96 not divisible by 64
    ref = dot_product_attention(q, k, v, backend="xla", causal=True)
    out = dot_product_attention(q, k, v, backend="flash", causal=True,
                                geometry_spec="block_q=64,block_k=64")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    out = dot_product_attention(q, k, v, backend="flash", causal=True,
                                block_q=64, block_k=64)  # XLA fallback path
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
