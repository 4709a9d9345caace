"""The expanded walk's kernel (``ops/pallas/latent_walk.py``) in its causal
form, interpreted: a plain latent layer's chunk against XLA's loops
(``models/deepseek_v3.py::expanded_walk``) and against a dense float32
softmax over the whole pool; the two forms of the one body against each
other; the layer that takes the kernel on the chip against the same layer off
it. (The selected form under an indexed layer's mask:
``tests/unit/models/test_dots3_note.py``.)
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import deepseek_v3 as package
from deepspeed_tpu.ops.pallas import latent_walk

DN, DR, DV, RANK = 16, 8, 16, 32


def _int(*values):
    return jnp.asarray(values, jnp.int32)


def _operands(heads, slots, l, positions, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = jax.random.normal(keys[0], (slots, RANK + DR, positions))
    q_nope = jax.random.normal(keys[1], (slots, l, heads, DN))
    q_rope = jax.random.normal(keys[2], (slots, l, heads, DR))
    w_kvb = jax.random.normal(keys[3], (RANK, heads, DN + DV)) * RANK ** -0.5
    return q_nope, q_rope, pool, w_kvb


def _dense(q_nope, q_rope, pool, w_kvb, start):
    """Causal softmax attention over each sequence's whole pool, every key and
    value made at once, float32 at the highest precision: [b, l, H, dv]."""
    l, positions = q_nope.shape[1], pool.shape[-1]
    kv = jnp.einsum("chd,bcp->bhdp", w_kvb, pool[:, :RANK], precision="highest")
    scores = (jnp.einsum("blhd,bhdp->bhlp", q_nope, kv[:, :, :DN], precision="highest")
              + jnp.einsum("blhd,bdp->bhlp", q_rope, pool[:, RANK:], precision="highest"))
    seen = jnp.arange(positions)[None, None, :] <= (start[:, None] + jnp.arange(l))[..., None]
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores * (DN + DR) ** -0.5, -jnp.inf), axis=-1)
    return jnp.einsum("bhlp,bhdp->blhd", probs, kv[:, :, DN:], precision="highest")


def _one_slot(operands, s, first, end, block, group=8, **kwargs):
    q_nope, q_rope, pool, w_kvb = operands
    blocks, block = latent_walk.walk_blocks(end, pool.shape[-1], block)
    out = latent_walk.causal_walk(
        jnp.moveaxis(q_nope[s], 1, 0), jnp.moveaxis(q_rope[s], 1, 0), jnp.swapaxes(w_kvb, 0, 1),
        pool, first, s, blocks, scale=(DN + DR) ** -0.5, block=block, group=group, **kwargs)
    return jnp.moveaxis(out, 0, 1)                                         # [l, H, dv]


#: (start, fed) of a slot's chunk of 16 queries over a pool of 64 in blocks of 16
CHUNKS = {
    "starts_on_a_block_boundary": (32, 16),
    "starts_inside_a_block": (21, 16),
    "starts_at_zero": (0, 16),
    "a_padded_last_chunk": (32, 9),
    "a_live_length_inside_a_block": (5, 6),
    "ends_with_the_pool": (48, 16),
}


@pytest.mark.parametrize("heads", [32, 8])
@pytest.mark.parametrize("chunk", sorted(CHUNKS))
def test_the_causal_form_is_the_expanded_walk_and_the_dense_softmax(chunk, heads):
    start, fed = CHUNKS[chunk]
    operands = _operands(heads, 2, 16, 64, seed=len(chunk))
    starts, feds = _int(7, start), _int(0, fed)
    got = _one_slot(operands, 1, starts[1], starts[1] + feds[1], 16)
    want = package.expanded_walk(*operands, starts, feds, 16)
    np.testing.assert_allclose(got[:fed], want[1, :fed], atol=2e-6)
    np.testing.assert_allclose(got[:fed], _dense(*operands, starts)[1, :fed], atol=1e-5)


@pytest.mark.parametrize("heads", [32, 8])
def test_a_pool_of_one_block_and_a_slot_that_is_fed_nothing(heads):
    """Through the loop over slots the layer runs (``kernel_walk``): the
    block is the kernel's own, here the whole pool; a parked slot (``fed`` 0,
    its position past the pool) is zeros."""
    operands = _operands(heads, 3, 16, 64, seed=heads)
    start, fed = _int(40, 64, 0), _int(16, 0, 11)
    got = package.kernel_walk(*operands, start, fed)
    want = package.expanded_walk(*operands, start, fed, 16)
    for s, real in ((0, 16), (2, 11)):
        np.testing.assert_allclose(got[s, :real], want[s, :real], atol=2e-6)
        np.testing.assert_allclose(got[s, :real], _dense(*operands, start)[s, :real], atol=1e-5)
    assert not np.asarray(got[1]).any()


def test_blocks_past_the_live_length_are_not_read():
    """What lies past ``start + fed``'s block never reaches a real query:
    NaNs there change nothing."""
    q_nope, q_rope, pool, w_kvb = _operands(8, 1, 16, 64)
    poisoned = pool.at[:, :, 32:].set(jnp.nan)
    for block in (16, 32):
        clean = _one_slot((q_nope, q_rope, pool, w_kvb), 0, 9, 25, block)
        got = _one_slot((q_nope, q_rope, poisoned, w_kvb), 0, 9, 25, block)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("start", [0, 21, 32])
def test_the_two_forms_are_one_body(start):
    """The selected form handed the causal mask computes what the causal form
    reads off the positions: the blocks before the chunk lose a mask that hid
    nothing, the chunk's own the second ``where`` of exact zeros. (To the last
    place or two: interpreted, XLA fuses the two bodies' arithmetic apart.)"""
    operands = q_nope, q_rope, pool, w_kvb = _operands(8, 2, 16, 64, seed=3)
    may = (jnp.arange(64)[None, :] <= start + jnp.arange(16)[:, None]).astype(jnp.float32)
    blocks, block = latent_walk.walk_blocks(start + 16, 64, 16)
    handed = latent_walk.selected_walk(
        jnp.moveaxis(q_nope[1], 1, 0), jnp.moveaxis(q_rope[1], 1, 0), jnp.swapaxes(w_kvb, 0, 1),
        pool, may, 1, blocks, scale=(DN + DR) ** -0.5, block=block)
    read_off = _one_slot(operands, 1, start, start + 16, 16)
    np.testing.assert_allclose(jnp.moveaxis(handed, 0, 1), read_off, rtol=0, atol=5e-7)


def test_the_shapes_the_kernel_takes_and_what_it_asks_of_vmem():
    # the long-document cell's plain layers; a ragged chunk; heads that fill no group
    assert latent_walk.takes(512, 32, 16384) and not latent_walk.takes(500, 32, 16384)
    assert not latent_walk.takes(512, 12, 16384) and not latent_walk.takes(512, 32, 16384 + 256)
    assert latent_walk.takes(16, 4, 64)                      # fewer heads than a group: one group
    # from the shapes alone, over what the chip's compiler allocates (24.6 and 16.6 MB
    # at the two cells' shapes), and growing with the chunk
    cells = [latent_walk._vmem_limit(8, 512, 128, 64, 128, 576, 512, masked=False),
             latent_walk._vmem_limit(8, 256, 128, 64, 128, 576, 512, masked=True)]
    assert cells == [48 << 20] * 2
    assert 64e6 < latent_walk._vmem_limit(8, 1024, 128, 64, 128, 576, 512, masked=False) < 100e6


@pytest.mark.parametrize("fed", [(0, 16, 9, 16), (16, 16, 16, 16)])
def test_a_plain_layer_takes_the_kernel_on_the_chip_and_gives_the_same_rows(monkeypatch, fed):
    """One plain layer of the test family over a serving cache, a chunk of 16:
    as on the chip (the kernel, interpreted here) and as off it (XLA's loops):
    the same rows out and the same latent written; the kernel is counted
    reading its own blocks (the whole pool of 128 here, for XLA's 16s)."""
    from deepspeed_tpu.inference.serving import programs as serving
    from deepspeed_tpu.ops.pallas import backend
    cfg = package.get_deepseek_v3_config("deepseek-v3-test", decode_cache_len=128)

    class OneLayer(nn.Module):
        @nn.compact
        def __call__(self, x, decode=True):
            index = self.variable("cache", "position_index", lambda: jnp.zeros([], jnp.int32))
            length = self.variable("cache", "chunk_length", lambda: jnp.zeros([], jnp.int32))
            # (the slot cache is made from a call with token ids)
            return package.LatentAttention(cfg, cfg.kind_of(0), name="self_attn")(
                x if x.ndim == 3 else jax.nn.one_hot(x, cfg.hidden_size), decode,
                length.value if index.value.ndim else None)

    layer = OneLayer()
    params = jax.tree.map(lambda p: p * 3.0 if p.ndim >= 2 else p, nn.meta.unbox(layer.init(
        jax.random.PRNGKey(4), jnp.zeros((1, 8, cfg.hidden_size)), decode=False)["params"]))
    cache, _ = serving.without_next_tokens(serving.make_slot_cache(layer, 4))
    rng = np.random.default_rng(6)
    cache = jax.tree.map(lambda leaf: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
                         if leaf.ndim == 4 else leaf, cache)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.hidden_size)), jnp.float32)
    start, fed = _int(100, 70, 0, 33), _int(*fed)
    start = jnp.where(fed > 0, start, 128)

    def run():
        held = serving.with_write_positions(cache, start, fed)
        out, state = layer.apply({"params": params, "cache": held}, x, mutable=["cache"])
        return out, state["cache"]["self_attn"]

    want = run()
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "interpret_default", lambda: True)
    walked = []
    kernel_walk = package.kernel_walk
    monkeypatch.setattr(package, "kernel_walk", lambda *a: walked.append(1) or kernel_walk(*a))
    got = run()
    assert walked
    real = np.asarray(fed) > 0
    np.testing.assert_allclose(np.asarray(got[0])[real], np.asarray(want[0])[real], atol=3e-5)
    np.testing.assert_array_equal(got[1]["cached_latent"], want[1]["cached_latent"])
    ends = np.where(real, np.asarray(start + fed), 0)
    read, live, written = (int(n) for n in got[1]["latent_reads"])
    assert (read, live) == (real.sum() * 128, ends.sum())
    assert int(want[1]["latent_reads"][0]) == (-(-ends // 16) * 16).sum()
    assert written == int(want[1]["latent_reads"][2])
