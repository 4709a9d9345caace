"""Kernel-parity tests: Pallas flash attention vs XLA reference.

Mirrors the reference's kernel-vs-torch parity strategy
(``tests/unit/ops/transformer/inference``, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.transformer.attention import dot_product_attention, xla_attention


def _rand_qkv(rng, b, l, h, d, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, l, h, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 128, 4, 32), (1, 256, 2, 64)])
def test_flash_forward_matches_xla(shape, causal):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, *shape)
    ref = dot_product_attention(q, k, v, backend="xla", causal=causal)
    out = dot_product_attention(q, k, v, backend="flash", causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# Every kind of tile (dead, interior, edge) in the forward and in both
# backward kernels, against the XLA reference. ``blocks`` are explicit block
# kwargs; a case without them runs the shape's default geometry, whose
# blocks are walked in compute tiles of 256: at 1,024 positions a causal
# head has 6 dead, 6 interior and 4 edge tiles.
_PARITY_CASES = {
    "causal": dict(shape=(1, 128, 2, 32), blocks=32),
    "full": dict(shape=(1, 128, 2, 32), blocks=32, causal=False),
    "defaults_causal": dict(shape=(1, 1024, 2, 32)),
    "defaults_lq_lt_lk": dict(shape=(1, 512, 1, 32), lk=1024),
    # prefixes end inside a tile (300), on a tile's edge (256), before one (200)
    "defaults_kv_lengths": dict(shape=(4, 512, 1, 32), causal=False,
                                kv_lengths=[200, 256, 300, 512]),
    "defaults_causal_kv_lengths": dict(shape=(2, 768, 1, 32), kv_lengths=[300, 700]),
    # the window's lower edge cuts tiles 0 and 1 of the last q tile, 2 is interior
    "defaults_window": dict(shape=(1, 1024, 1, 32), window=600),
    "defaults_bf16": dict(shape=(1, 1024, 2, 64), dtype=jnp.bfloat16, tol=3e-2),
    "defaults_bf16_head128": dict(shape=(1, 512, 1, 128), dtype=jnp.bfloat16, tol=3e-2),
    "small_tile_window": dict(shape=(1, 512, 1, 32), window=200, tile=64),
}


@pytest.mark.parametrize("case", list(_PARITY_CASES))
def test_flash_backward_matches_xla(case):
    spec = dict(_PARITY_CASES[case])
    b, lq, h, d = spec.pop("shape")
    lk = spec.pop("lk", lq)
    dtype = spec.pop("dtype", jnp.float32)
    tol = spec.pop("tol", None)
    causal = spec.pop("causal", True)
    blocks = spec.pop("blocks", None)
    if blocks:
        spec.update(block_q=blocks, block_k=blocks)
    lengths = spec.pop("kv_lengths", None)
    window = spec.pop("window", None)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, lq, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, lk, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, lk, h, d)), dtype)
    masks = dict(causal=causal, window=window)
    row_ok = True
    if lengths is not None:
        masks["kv_lengths"] = jnp.asarray(lengths, jnp.int32)
        # only rows inside each sequence's valid prefix are meaningful
        row_ok = (jnp.arange(lq)[None, :] < masks["kv_lengths"][:, None])[..., None, None]

    def loss(fn):
        def wrapped(q, k, v):
            o = jnp.where(row_ok, fn(q, k, v), 0).astype(jnp.float32)
            return (o * jnp.sin(jnp.arange(o.size).reshape(o.shape))).sum(), o
        return wrapped

    ref_fn = loss(lambda q, k, v: xla_attention(q, k, v, **masks))
    fl_fn = loss(lambda q, k, v: flash_attention(q, k, v, interpret=True, **masks, **spec))
    ref_grads, ref_o = jax.grad(ref_fn, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    fl_grads, fl_o = jax.grad(fl_fn, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(fl_o), np.asarray(ref_o),
                               atol=tol or 2e-5, rtol=tol or 2e-5)
    for rg, fg, name in zip(ref_grads, fl_grads, "qkv"):
        rg, fg = np.asarray(rg, np.float32), np.asarray(fg, np.float32)
        if tol:  # bf16: against the gradient's own size, as one rounding is
            np.testing.assert_allclose(fg, rg, atol=tol * np.abs(rg).max(), rtol=tol,
                                       err_msg=f"d{name} mismatch")
        else:
            np.testing.assert_allclose(fg, rg, atol=5e-5, rtol=5e-5,
                                       err_msg=f"d{name} mismatch")


def test_tile_counts_at_trace_time():
    """1,024 causal positions in tiles of 256: each of the four q tiles has
    its diagonal tile as an edge, the tiles left of it interior and the
    tiles right of it dead, 4 + 6 + 6 of 16 a head; the dq kernel walks the
    same tiles and the dkv kernel their transpose. A window of 600 turns
    tiles (2,0), (3,0) and (3,1) from interior to edge. Where one grid step
    holds the whole sequence and the walk is straight-line code, the dkv
    kernel steps in register tiles of 128: 28 dead, 28 interior, 8 edge."""
    from deepspeed_tpu.ops.pallas.flash_attention import _flash_bwd, _flash_fwd
    from deepspeed_tpu.utils.trace import recorder

    b, h, l, d = 2, 3, 1024, 64
    x = jax.ShapeDtypeStruct((b, h, l, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((b, h, l), jnp.float32)

    def counted(fn, *args):
        before = dict(recorder().counters)
        jax.eval_shape(fn, *args)
        return tuple((recorder().counters.get(f"attn_tiles_{kind}", 0)
                      - before.get(f"attn_tiles_{kind}", 0)) // (b * h)
                     for kind in ("dead", "interior", "edge"))

    def fwd(window=None, blk=1024):
        return counted(lambda q, k, v: _flash_fwd(q, k, v, 0.125, True, blk, blk, 256, True,
                                                  window=window), x, x, x)

    def bwd(window=None, blk_q=512):
        return counted(lambda q, k, v, o, s, g: _flash_bwd(
            (q, k, v, o, s, None), g, 0.125, True, blk_q, 1024, 256, True, window=window),
            x, x, x, x, lse, x)

    assert fwd() == fwd(blk=512) == (6, 6, 4)  # the blocks do not change the tiles
    assert bwd() == (12, 12, 8)
    assert fwd(window=600) == (6, 3, 7)
    assert bwd(window=600) == (12, 6, 14)
    assert bwd(blk_q=1024) == (6 + 28, 6 + 28, 4 + 8)


@pytest.mark.parametrize("walk", ["k", "q"])
def test_tile_classifier_matches_brute_force(walk):
    """`_k_walk` / `_q_walk` against the mask itself, tile by tile."""
    from deepspeed_tpu.ops.pallas.flash_attention import _k_walk, _q_walk

    rng = np.random.default_rng(5)
    for _ in range(300):
        tq, tk = (int(rng.choice([8, 16, 32])) for _ in range(2))
        nq, extra = int(rng.integers(1, 6)), int(rng.integers(0, 4))
        lq = nq * tq
        lk = -(-(lq + extra * tq) // tk) * tk
        off = lk - lq
        causal = bool(rng.integers(0, 2))
        window = int(rng.integers(1, lk + 8)) if causal and rng.integers(0, 2) else None
        ahead = (np.arange(lq)[:, None] + off) - np.arange(lk)[None, :]
        live = np.ones((lq, lk), bool)
        if causal:
            live &= ahead >= 0
        if window is not None:
            live &= ahead < window
        tiles = live.reshape(nq, tq, lk // tk, tk).transpose(0, 2, 1, 3).reshape(nq, lk // tk, -1)
        if walk == "q":
            tiles = tiles.transpose(1, 0, 2)
        for t, row in enumerate(tiles):
            if walk == "k":
                lo, ilo, ihi, hi = _k_walk(t * tq, tq, tk, lk // tk, off, causal, window, None)
            else:
                lo, ilo, ihi, hi = _q_walk(t * tk, tk, tq, nq, off, causal, window, None)
            assert 0 <= lo <= ilo <= ihi <= hi <= len(row)
            for u, tile in enumerate(row):
                kind = ("dead" if not lo <= u < hi else
                        "interior" if ilo <= u < ihi else "edge")
                want = "dead" if not tile.any() else "interior" if tile.all() else "edge"
                # a tile may be masked though it needs none, never the reverse
                assert kind == want or (kind, want) == ("edge", "interior"), \
                    (walk, tq, tk, lq, lk, causal, window, t, u, kind, want)


def test_flash_decode_offset():
    """lq < lk (kv-cache decode): causal offset must line up."""
    rng = np.random.default_rng(2)
    b, h, d = 1, 2, 32
    q = jnp.asarray(rng.standard_normal((b, 8, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, 64, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, 64, h, d)), jnp.float32)
    ref = dot_product_attention(q, k, v, backend="xla", causal=True)
    out = dot_product_attention(q, k, v, backend="flash", causal=True, block_q=8, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_bf16_close():
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, 1, 128, 2, 64, jnp.bfloat16)
    ref = dot_product_attention(q, k, v, backend="xla", causal=True)
    out = dot_product_attention(q, k, v, backend="flash", causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2)


def test_flash_fallback_with_mask():
    """bias/mask/dropout route to the XLA backend (feature fallback)."""
    rng = np.random.default_rng(4)
    q, k, v = _rand_qkv(rng, 1, 64, 2, 32)
    mask = jnp.ones((1, 1, 64, 64), bool)
    ref = dot_product_attention(q, k, v, backend="xla", causal=True, mask=mask)
    out = dot_product_attention(q, k, v, backend="flash", causal=True, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------- decode
def test_flash_decode_matches_xla_varying_lengths():
    """Per-sequence lengths: each row attends to its own live prefix only."""
    rng = np.random.default_rng(3)
    b, lkv, h, d = 4, 256, 2, 32
    lengths = jnp.asarray([5, 64, 200, 256], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    ref = dot_product_attention(q, k, v, backend="xla", causal=False,
                                decode_lengths=lengths)
    out = dot_product_attention(q, k, v, backend="flash", causal=False,
                                decode_lengths=lengths, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_decode_multi_token_append():
    """lq>1 (chunked prefill / speculative step): row i of q sits at global
    position length - lq + i and must only see positions <= its own."""
    rng = np.random.default_rng(4)
    b, lq, lkv, h, d = 2, 8, 128, 3, 16
    lengths = jnp.asarray([32, 128], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, lq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    ref = dot_product_attention(q, k, v, backend="xla", causal=False,
                                decode_lengths=lengths)
    out = dot_product_attention(q, k, v, backend="flash", causal=False,
                                decode_lengths=lengths, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_decode_ignores_dead_cache():
    """Garbage beyond a sequence's length must not leak into the output."""
    rng = np.random.default_rng(5)
    b, lkv, h, d = 1, 128, 1, 16
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    lengths = jnp.asarray([40], jnp.int32)
    out1 = dot_product_attention(q, k, v, backend="flash", causal=False,
                                 decode_lengths=lengths, block_k=32)
    poison = jnp.full_like(k[:, 40:], 1e4)
    k2 = k.at[:, 40:].set(poison)
    v2 = v.at[:, 40:].set(poison)
    out2 = dot_product_attention(q, k2, v2, backend="flash", causal=False,
                                 decode_lengths=lengths, block_k=32)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_flash_decode_bf16():
    rng = np.random.default_rng(6)
    b, lkv, h, d = 2, 128, 2, 32
    lengths = jnp.asarray([17, 99], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.bfloat16)
    ref = dot_product_attention(q, k, v, backend="xla", causal=False,
                                decode_lengths=lengths)
    out = dot_product_attention(q, k, v, backend="flash", causal=False,
                                decode_lengths=lengths, block_k=64)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_flash_decode_fully_masked_rows_are_zero():
    """lq > lengths[b]: rows with no live positions return zeros (documented
    contract) instead of a bogus average of dead cache slots."""
    rng = np.random.default_rng(7)
    b, lq, lkv, h, d = 1, 4, 64, 1, 16
    q = jnp.asarray(rng.standard_normal((b, lq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, lkv, h, d)), jnp.float32)
    lengths = jnp.asarray([2], jnp.int32)
    out = dot_product_attention(q, k, v, backend="flash", causal=False,
                                decode_lengths=lengths, block_k=32)
    # rows 0,1 sit at q_pos -2,-1 -> fully masked -> zeros
    np.testing.assert_array_equal(np.asarray(out[:, :2]), np.zeros((b, 2, h, d), np.float32))
    # rows 2,3 are live and must be finite/nonzero
    assert np.abs(np.asarray(out[:, 2:])).max() > 0


# ---------------------------------------------------------------------------
# padding-mask (kv_lengths) support: fwd + bwd parity vs XLA with a mask
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_kv_lengths_matches_masked_xla(causal):
    rng = np.random.default_rng(10)
    b, l, h, d = 4, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    lengths = jnp.asarray([256, 200, 129, 64], jnp.int32)
    pad = (jnp.arange(l)[None, :] < lengths[:, None])[:, None, None, :]

    got = flash_attention(q, k, v, causal=causal, kv_lengths=lengths,
                          block_q=128, block_k=128, interpret=True)
    want = xla_attention(q, k, v, causal=causal, mask=pad)
    # only rows inside each sequence's valid prefix are meaningful
    row_ok = (jnp.arange(l)[None, :] < lengths[:, None])[..., None, None]
    np.testing.assert_allclose(np.asarray(jnp.where(row_ok, got, 0)),
                               np.asarray(jnp.where(row_ok, want, 0)),
                               rtol=2e-5, atol=2e-5)


def test_kv_lengths_grad_parity():
    rng = np.random.default_rng(11)
    b, l, h, d = 2, 256, 2, 32
    q = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
    lengths = jnp.asarray([256, 130], jnp.int32)
    pad = (jnp.arange(l)[None, :] < lengths[:, None])[:, None, None, :]
    # only valid rows feed the loss, mirroring a padded-batch training step
    row_ok = (jnp.arange(l)[None, :] < lengths[:, None])[..., None, None]

    def loss_flash(q_, k_, v_):
        o = flash_attention(q_, k_, v_, causal=False, kv_lengths=lengths,
                            block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.where(row_ok, o, 0) ** 2)

    def loss_xla(q_, k_, v_):
        o = xla_attention(q_, k_, v_, causal=False, mask=pad)
        return jnp.sum(jnp.where(row_ok, o, 0) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name}")


def test_bert_padding_uses_flash_natively():
    """BERT with a [B, L] padding mask under the flash backend matches the
    XLA backend — and padded positions don't change valid outputs."""
    from deepspeed_tpu.models.bert import BertForMaskedLM, get_bert_config

    rng = np.random.default_rng(12)
    ids = jnp.asarray(rng.integers(0, 250, (2, 128)), jnp.int32)
    mask = jnp.asarray([[1] * 128, [1] * 70 + [0] * 58], jnp.int32)
    logits = {}
    for backend in ("xla", "flash"):
        cfg = get_bert_config("test", attention_backend=backend)
        model = BertForMaskedLM(cfg)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        logits[backend] = model.apply({"params": params}, ids, attention_mask=mask)
    np.testing.assert_allclose(np.asarray(logits["flash"][:, :70]),
                               np.asarray(logits["xla"][:, :70]),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# sliding-window attention (Mistral semantics): fwd + bwd parity vs XLA
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [64, 200])
def test_sliding_window_matches_xla(window):
    rng = np.random.default_rng(20)
    b, l, h, d = 2, 256, 2, 32
    q, k, v = _rand_qkv(rng, b, l, h, d)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    want = xla_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_grad_parity():
    rng = np.random.default_rng(21)
    b, l, h, d, w = 2, 256, 2, 32, 100
    q, k, v = _rand_qkv(rng, b, l, h, d)

    def loss(fn):
        def f(q_, k_, v_):
            return jnp.sum(fn(q_, k_, v_) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gf = loss(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True, window=w, block_q=64, block_k=64, interpret=True))
    gx = loss(lambda q_, k_, v_: xla_attention(q_, k_, v_, causal=True, window=w))
    for a, bb, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name}")


def test_window_requires_causal():
    rng = np.random.default_rng(22)
    q, k, v = _rand_qkv(rng, 1, 128, 2, 32)
    with pytest.raises(ValueError, match="requires causal"):
        flash_attention(q, k, v, causal=False, window=32, interpret=True)


def test_mistral_preset_runs_with_window():
    """The Mistral preset (sliding_window) trains a step end-to-end."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config
    import deepspeed_tpu

    cfg = get_llama_config("test", sliding_window=32, dtype=jnp.bfloat16,
                           attention_backend="flash")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10**9})
    rng = np.random.default_rng(23)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 128)).astype(np.int32)}
    from deepspeed_tpu.utils.trace import recorder
    before = dict(recorder().counters)
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # tracing the step counted the tiles its attention kernels will run
    assert recorder().counters.get("attn_tiles_edge", 0) > before.get("attn_tiles_edge", 0)
    assert {"attn_tiles_dead", "attn_tiles_interior"} <= set(recorder().counters)
    # config table carries the real preset
    assert get_llama_config("mistral-7b").sliding_window == 4096


def test_window_composes_with_kv_lengths():
    """Padded prefill with a sliding window: both bounds interact (a short
    padded row's window can start past its valid prefix) — parity vs XLA
    with the same masks, on the valid rows."""
    rng = np.random.default_rng(24)
    b, l, h, d, w = 3, 256, 2, 32, 96
    q, k, v = _rand_qkv(rng, b, l, h, d)
    lengths = jnp.asarray([256, 100, 40], jnp.int32)
    got = flash_attention(q, k, v, causal=True, window=w, kv_lengths=lengths,
                          block_q=64, block_k=64, interpret=True)
    want = xla_attention(q, k, v, causal=True, window=w, kv_lengths=lengths)
    row_ok = (jnp.arange(l)[None, :] < lengths[:, None])[..., None, None]
    np.testing.assert_allclose(np.asarray(jnp.where(row_ok, got, 0)),
                               np.asarray(jnp.where(row_ok, want, 0)),
                               rtol=2e-5, atol=2e-5)
    # and the gradients agree on the same composition
    def loss(fn):
        def f(q_, k_, v_):
            return jnp.sum(jnp.where(row_ok, fn(q_, k_, v_), 0) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gf = loss(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True, window=w, kv_lengths=lengths,
        block_q=64, block_k=64, interpret=True))
    gx = loss(lambda q_, k_, v_: xla_attention(
        q_, k_, v_, causal=True, window=w, kv_lengths=lengths))
    for a, bb, name in zip(gf, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=3e-4,
                                   err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# grouped-query heads inside the kernels, with and without a window, against
# dense attention with the mask written out (no backend of the package)
# ---------------------------------------------------------------------------
def _dense_masked(q, k, v, window):
    """softmax(q k^T / sqrt(d) + mask) v over BLHD operands in float32, query
    head ``h`` reading key head ``h // (H / Hk)``; ``window`` None = causal."""
    b, l, h, d = q.shape
    n_rep = h // k.shape[2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, n_rep, axis=2)) / np.sqrt(d)
    at = np.arange(l)
    live = at[None, :] <= at[:, None]
    if window is not None:
        live &= at[None, :] > at[:, None] - window
    probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, n_rep, axis=2))


@pytest.mark.parametrize("window", [None, 96], ids=["full", "window96"])
def test_grouped_query_heads_forward_and_gradients(window):
    """4 query heads on 2 key heads, 256 positions in 64-position blocks: with
    the window of 96 the key block 0 lies wholly below the window of query
    blocks 2 and 3 (a dead tile on the lower side), block 1 straddles its
    edge. dq, dk and dv against the dense form's, dk and dv summed over the
    two query heads that share a key head."""
    rng = np.random.default_rng(23)
    b, l, h, hk, d = 2, 256, 4, 2, 32
    q, _, _ = _rand_qkv(rng, b, l, h, d)
    k, v, _ = _rand_qkv(rng, b, l, hk, d)
    flash = lambda q_, k_, v_: flash_attention(  # noqa: E731
        q_, k_, v_, causal=True, window=window, block_q=64, block_k=64, interpret=True)
    dense = lambda q_, k_, v_: _dense_masked(q_, k_, v_, window)  # noqa: E731
    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    weight = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(dense), "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4,
                                   err_msg=f"d{name}")


def test_grouped_query_heads_fall_back_with_the_heads_repeated():
    """A call the kernel does not cover (a mask) goes to XLA with as many key
    heads as query heads."""
    rng = np.random.default_rng(24)
    q, _, _ = _rand_qkv(rng, 1, 64, 4, 32)
    k, v, _ = _rand_qkv(rng, 1, 64, 2, 32)
    mask = jnp.ones((1, 1, 64, 64), bool)
    got = flash_attention(q, k, v, causal=True, mask=mask, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense_masked(q, k, v, None)),
                               rtol=2e-5, atol=2e-5)
