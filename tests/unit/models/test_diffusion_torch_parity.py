"""Torch-vs-flax numerics parity for the diffusion family (r4 verdict
Weak #8: the text families have torch logits-parity tests; diffusion did
not). diffusers itself is not installed in this image, so the independent
reference is a FUNCTIONAL torch re-implementation of the same architecture
(torch convs/norms/attention in NCHW) consuming the flax params directly —
this catches transpose/layout bugs (HWIO vs OIHW, Dense kernel
orientation, attention head folding), epsilon mismatches (flax GroupNorm/
LayerNorm default 1e-6 vs torch 1e-5) and activation-placement drift,
exactly what an HF-weight import must get right.

Weight orientation contract (== what a diffusers state_dict importer
applies in reverse):
- ``nn.Conv`` kernel HWIO  <-> torch conv weight OIHW (permute 3,2,0,1)
- ``nn.Dense`` kernel (in, out) <-> torch linear weight (out, in)
- attention ``DenseGeneral`` (in, heads, kv) <-> torch (heads*kv, in)
- SAME padding at stride 2 pads asymmetrically (right/bottom) — torch
  side must F.pad (0,1,0,1) + valid conv, NOT padding=1.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402

from deepspeed_tpu.models.diffusion import (AutoencoderKL, UNet2DConditionModel,
                                            UNetConfig, VAEConfig,
                                            timestep_embedding)

# ---------------------------------------------------------------------------
# functional torch mirrors, reading the flax param tree


def _t(x):
    return torch.from_numpy(np.asarray(x, np.float32))


def t_conv(p, x, stride=1):
    w = _t(p["kernel"]).permute(3, 2, 0, 1)  # HWIO -> OIHW
    b = _t(p["bias"]) if "bias" in p else None
    k = w.shape[-1]
    if stride == 2:
        # jax SAME at stride 2 (even input): pad_total=1 -> before 0, after 1
        x = F.pad(x, (0, 1, 0, 1))
        return F.conv2d(x, w, b, stride=2)
    return F.conv2d(x, w, b, padding=k // 2)


def t_dense(p, x):
    w = _t(p["kernel"])
    if w.ndim == 3:  # (in, heads, kv): q/k/v projection
        w = w.reshape(w.shape[0], -1)
    elif w.ndim != 2:
        raise AssertionError(w.shape)
    y = x @ w
    if "bias" in p:
        y = y + _t(p["bias"]).reshape(-1)
    return y


def t_groupnorm(p, x, groups):
    p = p.get("GroupNorm_0", p)  # GroupNorm32 wraps an inner nn.GroupNorm
    return F.group_norm(x, groups, _t(p["scale"]), _t(p["bias"]), eps=1e-6)


def t_layernorm(p, x):
    return F.layer_norm(x, (x.shape[-1],), _t(p["scale"]), _t(p["bias"]), eps=1e-6)


def t_resnet(p, x, temb, groups):
    h = t_conv(p["conv1"], F.silu(t_groupnorm(p["norm1"], x, groups)))
    if temb is not None:
        shift = t_dense(p["time_emb_proj"], F.silu(temb))
        h = h + shift[:, :, None, None]
    h = t_conv(p["conv2"], F.silu(t_groupnorm(p["norm2"], h, groups)))
    if "conv_shortcut" in p:
        x = t_conv(p["conv_shortcut"], x)
    return x + h


def t_attn(p, name, q_src, kv_src, heads):
    c = q_src.shape[-1]
    hd = c // heads

    def proj(key, src):
        w = _t(p[f"{name}_{key}"]["kernel"])  # (in, heads, kv)
        return (src @ w.reshape(w.shape[0], -1)).reshape(*src.shape[:-1], heads, hd)

    q, k, v = proj("q", q_src), proj("k", kv_src), proj("v", kv_src)
    scores = torch.einsum("blhd,bmhd->bhlm", q, k) / (hd ** 0.5)
    o = torch.einsum("bhlm,bmhd->blhd", scores.softmax(-1), v)
    wo = _t(p[f"{name}_out"]["kernel"]).reshape(-1, c)  # (heads*kv, embed)
    return o.reshape(*o.shape[:-2], heads * hd) @ wo + _t(p[f"{name}_out"]["bias"])


def t_spatial_transformer(p, x, context, cfg):
    b, c, hgt, wid = x.shape
    heads = max(c // cfg.attention_head_dim, 1)
    resid = x
    h = t_groupnorm(p["norm"], x, cfg.norm_num_groups)
    h = h.permute(0, 2, 3, 1).reshape(b, hgt * wid, c)  # NCHW -> tokens
    h = h + t_attn(p, "self_attn", t_layernorm(p["ln1"], h), t_layernorm(p["ln1"], h), heads)
    ctx = h if context is None else context
    h = h + t_attn(p, "cross_attn", t_layernorm(p["ln2"], h), ctx, heads)
    gate = t_dense(p["ff_in"], t_layernorm(p["ln3"], h))
    a, g = gate.chunk(2, dim=-1)
    h = h + t_dense(p["ff_out"], a * F.gelu(g))
    return resid + h.reshape(b, hgt, wid, c).permute(0, 3, 1, 2)


def t_unet(params, sample_nchw, timesteps, context, cfg):
    ch0 = cfg.block_out_channels[0]
    temb = _t(timestep_embedding(timesteps, ch0))
    temb = t_dense(params["time_dense2"], F.silu(t_dense(params["time_dense1"], temb)))

    h = t_conv(params["conv_in"], sample_nchw)
    skips = [h]
    n_levels = len(cfg.block_out_channels)
    for i in range(n_levels):
        for j in range(cfg.layers_per_block):
            h = t_resnet(params[f"down_{i}_res_{j}"], h, temb, cfg.norm_num_groups)
            if i < n_levels - 1:
                h = t_spatial_transformer(params[f"down_{i}_attn_{j}"], h, context, cfg)
            skips.append(h)
        if i < n_levels - 1:
            h = t_conv(params[f"down_{i}_downsample"], h, stride=2)
            skips.append(h)
    h = t_resnet(params["mid_res_1"], h, temb, cfg.norm_num_groups)
    h = t_spatial_transformer(params["mid_attn"], h, context, cfg)
    h = t_resnet(params["mid_res_2"], h, temb, cfg.norm_num_groups)
    for i in reversed(range(n_levels)):
        for j in range(cfg.layers_per_block + 1):
            h = torch.cat([h, skips.pop()], dim=1)
            h = t_resnet(params[f"up_{i}_res_{j}"], h, temb, cfg.norm_num_groups)
            if i < n_levels - 1:
                h = t_spatial_transformer(params[f"up_{i}_attn_{j}"], h, context, cfg)
        if i > 0:
            h = F.interpolate(h, scale_factor=2, mode="nearest")
            h = t_conv(params[f"up_{i}_upsample"], h)
    h = F.silu(t_groupnorm(params["norm_out"], h, cfg.norm_num_groups))
    return t_conv(params["conv_out"], h)


def t_vae_stack(p, h, channels, downsample, cfg):
    n = len(channels)
    for i, _ch in enumerate(channels):
        for j in range(cfg.layers_per_block):
            h = t_resnet(p[f"res_{i}_{j}"], h, None, cfg.norm_num_groups)
        if i < n - 1:
            if downsample:
                h = t_conv(p[f"down_{i}"], h, stride=2)
            else:
                h = F.interpolate(h, scale_factor=2, mode="nearest")
                h = t_conv(p[f"up_{i}"], h)
    return h


def t_vae_roundtrip(params, x_nchw, cfg):
    h = t_vae_stack(params["encoder"], t_conv(params["conv_in"], x_nchw),
                    cfg.block_out_channels, True, cfg)
    moments = t_conv(params["quant_conv"], h)
    mean, _ = moments.chunk(2, dim=1)
    h = t_vae_stack(params["decoder"], t_conv(params["post_quant_conv"], mean),
                    tuple(reversed(cfg.block_out_channels)), False, cfg)
    return t_conv(params["conv_out"],
                  F.silu(t_groupnorm(params["norm_out"], h, cfg.norm_num_groups)))


# ---------------------------------------------------------------------------

def _unboxed(variables):
    import flax.linen as fnn
    return fnn.meta.unbox(variables["params"])


def test_unet_matches_functional_torch():
    cfg = UNetConfig(block_out_channels=(16, 32), attention_head_dim=8,
                     norm_num_groups=4, cross_attention_dim=16)
    model = UNet2DConditionModel(cfg)
    rng = np.random.default_rng(0)
    sample = rng.standard_normal((2, 16, 16, 4)).astype(np.float32)
    t = np.array([3.0, 250.0], np.float32)
    ctx = rng.standard_normal((2, 6, 16)).astype(np.float32)
    # init and apply jitted: eagerly each is dispatched an operation at a time
    params = _unboxed(jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(sample),
                                          jnp.asarray(t), jnp.asarray(ctx)))
    got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(sample),
                                          jnp.asarray(t), jnp.asarray(ctx)))
    with torch.no_grad():
        want = t_unet(params, _t(sample).permute(0, 3, 1, 2), t, _t(ctx), cfg)
    want = want.permute(0, 2, 3, 1).numpy()
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_unet_unconditional_matches_torch():
    cfg = UNetConfig(block_out_channels=(16, 32), attention_head_dim=8,
                     norm_num_groups=4)
    model = UNet2DConditionModel(cfg)
    rng = np.random.default_rng(1)
    sample = rng.standard_normal((1, 8, 8, 4)).astype(np.float32)
    t = np.array([17.0], np.float32)
    params = _unboxed(jax.jit(model.init)(jax.random.PRNGKey(1), jnp.asarray(sample),
                                          jnp.asarray(t)))
    got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(sample),
                                          jnp.asarray(t)))
    with torch.no_grad():
        want = t_unet(params, _t(sample).permute(0, 3, 1, 2), t, None, cfg)
    np.testing.assert_allclose(got, want.permute(0, 2, 3, 1).numpy(),
                               atol=2e-4, rtol=2e-4)


def test_vae_roundtrip_matches_torch():
    cfg = VAEConfig(block_out_channels=(16, 32), norm_num_groups=4)
    model = AutoencoderKL(cfg)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 16, 16, 3)).astype(np.float32)
    params = _unboxed(jax.jit(model.init)(jax.random.PRNGKey(2), jnp.asarray(x)))
    got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(x)))
    with torch.no_grad():
        want = t_vae_roundtrip(params, _t(x).permute(0, 3, 1, 2), cfg)
    np.testing.assert_allclose(got, want.permute(0, 2, 3, 1).numpy(),
                               atol=2e-4, rtol=2e-4)
