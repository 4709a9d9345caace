"""Diffusion family (reference ``model_implementations/diffusers/{unet,vae}.py``
serving wrappers + generic diffusers injection): flax UNet/VAE forward
contracts, serving-wrapper jit cache, and a denoising smoke loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.diffusion import (AutoencoderKL, DSUNet, DSVAE,
                                            UNet2DConditionModel, UNetConfig,
                                            VAEConfig, timestep_embedding)


def _unet():
    cfg = UNetConfig()
    m = UNet2DConditionModel(cfg)
    sample = jnp.zeros((2, 16, 16, cfg.in_channels))
    t = jnp.array([1, 5])
    ctx = jnp.zeros((2, 7, cfg.cross_attention_dim))
    params = jax.jit(m.init)(jax.random.PRNGKey(0), sample, t, ctx)["params"]
    return m, params, cfg


def test_timestep_embedding_shape_and_range():
    e = timestep_embedding(jnp.array([0, 10, 999]), 32)
    assert e.shape == (3, 32)
    assert np.all(np.abs(np.asarray(e)) <= 1.0 + 1e-6)


def test_unet_eps_prediction_contract():
    m, params, cfg = _unet()
    sample = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, cfg.in_channels))
    ctx = jax.random.normal(jax.random.PRNGKey(2), (2, 7, cfg.cross_attention_dim))
    apply = jax.jit(m.apply)    # one program for the three calls
    eps = apply({"params": params}, sample, jnp.array([3, 7]), ctx)
    assert eps.shape == (2, 16, 16, cfg.out_channels)
    assert np.isfinite(np.asarray(eps)).all()
    # conditioning matters: different context, different prediction
    eps2 = apply({"params": params}, sample, jnp.array([3, 7]), ctx + 1.0)
    assert not np.allclose(np.asarray(eps), np.asarray(eps2))
    # timestep matters
    eps3 = apply({"params": params}, sample, jnp.array([900, 950]), ctx)
    assert not np.allclose(np.asarray(eps), np.asarray(eps3))


def test_vae_encode_decode_shapes():
    cfg = VAEConfig()
    m = AutoencoderKL(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, cfg.in_channels))
    params = m.init(jax.random.PRNGKey(1), x)["params"]
    mean, logvar = m.apply({"params": params}, x, method="encode")
    # one downsample per level transition: 16 -> 8 spatial, latent channels
    assert mean.shape == (2, 8, 8, cfg.latent_channels) == logvar.shape
    recon = m.apply({"params": params}, mean, method="decode")
    assert recon.shape == x.shape
    roundtrip = m.apply({"params": params}, x)
    assert roundtrip.shape == x.shape and np.isfinite(np.asarray(roundtrip)).all()


def test_ds_wrappers_serve_and_cache():
    m, params, cfg = _unet()
    served = DSUNet(m, params)
    sample = jnp.zeros((1, 16, 16, cfg.in_channels))
    ctx = jnp.zeros((1, 7, cfg.cross_attention_dim))
    out = served(sample, jnp.array([1]), ctx)
    assert out.shape == (1, 16, 16, cfg.out_channels)
    n_after_first = len(served._fns)
    served(sample, jnp.array([2]), ctx)  # same shapes -> cached executable
    assert len(served._fns) == n_after_first
    served(jnp.zeros((2, 16, 16, cfg.in_channels)), jnp.array([1, 2]),
           jnp.zeros((2, 7, cfg.cross_attention_dim)))  # new shape -> new entry
    assert len(served._fns) == n_after_first + 1

    vcfg = VAEConfig()
    vm = AutoencoderKL(vcfg)
    x = jnp.zeros((1, 16, 16, vcfg.in_channels))
    vparams = vm.init(jax.random.PRNGKey(0), x)["params"]
    vs = DSVAE(vm, vparams)
    mean, _ = vs.encode(x)
    assert vs.decode(mean).shape == x.shape
    assert vs(x).shape == x.shape


def test_reference_import_paths():
    from deepspeed_tpu.model_implementations import DSUNet as A
    from deepspeed_tpu.model_implementations.diffusers.unet import DSUNet as B
    from deepspeed_tpu.model_implementations.diffusers.vae import DSVAE as C
    assert A is B is DSUNet and C is DSVAE


def test_denoising_smoke_loop():
    """A 4-step DDIM-ish loop through the served UNet stays finite and
    changes the latent — the serving contract a pipeline relies on."""
    m, params, cfg = _unet()
    served = DSUNet(m, params, dtype=jnp.float32)
    ctx = jax.random.normal(jax.random.PRNGKey(3), (1, 7, cfg.cross_attention_dim))
    z = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 16, cfg.in_channels))
    z0 = np.asarray(z).copy()
    for t in (800, 600, 400, 200):
        eps = served(z, jnp.array([t]), ctx)
        z = z - 0.1 * eps  # toy update; schedule math is pipeline-side
    assert np.isfinite(np.asarray(z)).all()
    assert not np.allclose(np.asarray(z), z0)
    assert len(served._fns) == 1  # every step replayed one executable
