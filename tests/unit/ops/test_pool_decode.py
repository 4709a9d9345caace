"""``ops/pallas/pool_decode.py`` (the Pallas interpreter off the chip) against
the XLA loop it replaces on a TPU, ``models/common.py`` ``cached_attention``'s
``l == 1`` branch: the same numbers, each slot read as far as that slot goes."""

from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import common
from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.ops.pallas.pool_decode import blocks_read, pool_decode

#: whose layer -> (key heads, head dim, query heads a key head): the two bodies
#: of the kernel, a key head at a time (``rep`` > 1) and every head in one matmul
#: (``rep`` 1), at the widths of the families that read through it
HEADS = {"laguna_full": (2, 128, 6), "laguna_window": (2, 128, 8), "gpt2": (16, 64, 1),
         "olmoe": (16, 128, 1), "nemotron": (2, 128, 16)}

#: layout -> (places, window, block handed, slots, rows, positions held a sequence)
LAYOUTS = {
    # a full pool, every slot a sequence: parked, one key, a block's edge, one
    # past it, the whole pool, and one inside a block
    "whole": (512, 512, 128, 6, None, [0, 1, 128, 129, 512, 300]),
    # a rung: a permuted quarter of the slots
    "quarter_rung": (512, 512, 128, 20, [13, 2, 19, 7, 0], [129, 512, 0, 1, 128]),
    # a window layer's ring, handed the cell's block (no divisor of 768: one
    # block, the ring): not yet full, full to the edge, wrapped once and often
    "wrapped_ring": (768, 512, 1024, 6, None, [0, 5, 512, 768, 769, 3000]),
    # a decode rung of a serving cache: eight of 32 slots, out of order, two
    # parked among them (their place is the sentinel, the pool's extent)
    "rung_of_eight": (256, 256, 128, 32, [30, 1, 3, 4, 17, 9, 22, 31],
                      [11, 256, 0, 128, 129, 0, 1, 200]),
}


def _operands(layout, pool, heads, seed=0):
    places, window, block, slots, rows, held = LAYOUTS[layout]
    KV, D, rep = HEADS[heads]
    rng = np.random.default_rng(seed)
    held = np.asarray(held)
    b = len(held)
    if pool == "int8":
        dtype = jnp.float32
        keys, values = (jnp.asarray(rng.integers(-127, 128, (slots, KV, D, places)), jnp.int8)
                        for _ in range(2))
        key_scale, value_scale = (jnp.asarray(rng.uniform(0.01, 0.02, (slots, KV, places)),
                                              jnp.float32) for _ in range(2))
    else:
        dtype = jnp.dtype(pool)
        keys, values = (jnp.asarray(rng.normal(size=(slots, KV, D, places)), dtype)
                        for _ in range(2))
        key_scale = value_scale = None
    q = jnp.asarray(rng.normal(size=(b, 1, KV * rep, D)), dtype)
    q_pos = jnp.asarray(np.where(held > 0, held - 1, 0 if rows is None else places)[:, None],
                        jnp.int32)
    fed = jnp.asarray(held > 0, jnp.int32)
    rows = None if rows is None else jnp.asarray(rows, jnp.int32)
    return (q, keys, key_scale, values, value_scale, q_pos, fed), dict(
        window=window, block=block, rows=rows), held, places


@pytest.mark.parametrize("pool", ["int8", "float32", "bfloat16"])
@pytest.mark.parametrize("layout, heads", [
    (layout, heads) for layout in ("whole", "quarter_rung", "wrapped_ring")
    for heads in ("laguna_full", "laguna_window")] + [
    ("rung_of_eight", heads) for heads in ("gpt2", "olmoe", "nemotron")])
def test_the_kernel_gives_the_loops_numbers_and_reads_each_slot_as_far_as_it_goes(layout, pool,
                                                                                  heads):
    (q, keys, key_scale, values, value_scale, q_pos, fed), how, held, places = _operands(
        layout, pool, heads)
    want, read_together = common.cached_attention(q, keys, key_scale, values, value_scale, q_pos,
                                                 fed, **how)
    got, read = pool_decode(q[:, 0], keys, key_scale, values, value_scale, q_pos[:, 0], fed, **how)
    assert got.dtype == q.dtype and got.shape == want[:, 0].shape
    got, want = np.asarray(got, np.float32), np.asarray(want[:, 0], np.float32)
    # the same blocks in the same order: the running softmax rounds alike
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 if pool != "bfloat16" else 4e-3)
    assert not got[held == 0].any() and np.abs(want[held > 0]).max(axis=(1, 2)).min() > 0.01
    # what the kernel was bounded to: each fed slot's own blocks; the loop
    # walks every slot as far as the longest
    block = how["block"] if places % how["block"] == 0 else places
    own = -(-np.minimum(held, places) // block)
    assert int(read) == own.sum() * block
    assert int(read_together) == own.max() * block * len(held) >= int(read)
    steps, block_ = blocks_read(q_pos[:, 0], fed, places, how["block"])
    assert block_ == block and np.asarray(steps).tolist() == own.tolist()


def test_a_slot_reads_its_own_row_under_its_own_mask_whatever_lies_past_its_end():
    """What the other rows and a slot's own dead tail hold changes nothing: a
    full pool's output is dense grouped-query attention over the sequence's
    live rows, dequantised; a wrapped ring's over the window's positions."""
    (q, keys, key_scale, values, value_scale, q_pos, fed), how, held, places = _operands(
        "quarter_rung", "int8", "laguna_full")
    KV, D, _ = HEADS["laguna_full"]
    got, _ = pool_decode(q[:, 0], keys, key_scale, values, value_scale, q_pos[:, 0], fed, **how)
    k, v = (np.asarray(c, np.float32) * np.asarray(s)[:, :, None, :]
            for c, s in ((keys, key_scale), (values, value_scale)))
    for s, row in enumerate(np.asarray(how["rows"])):
        for h in range(KV * 6):
            n = held[s]
            if not n:
                continue
            scores = np.asarray(q[s, 0, h]) @ k[row, h // 6, :, :n] / np.sqrt(D)
            weights = np.exp(scores - scores.max())
            np.testing.assert_allclose(np.asarray(got[s, h]),
                                       v[row, h // 6, :, :n] @ (weights / weights.sum()),
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("on_tpu", [False, True], ids=["off_the_chip", "on_a_tpu"])
def test_cached_attention_takes_the_kernel_for_one_query_a_sequence_on_a_tpu_alone(on_tpu):
    """Off the chip ``cached_attention`` traces the loop it traced, for a
    decode tick and a chunk alike; on a TPU a decode tick is one kernel (no
    loop), its ``read`` the kernel's bound, and a chunk's walk stays the loop."""
    (q, *rest), how, held, places = _operands("quarter_rung", "int8", "laguna_window")
    chunk = jnp.concatenate([q, q], axis=1)
    with mock.patch.object(backend, "on_tpu", lambda: on_tpu):
        tick = str(jax.make_jaxpr(lambda q: common.cached_attention(q, *rest, **how))(q))
        walk = str(jax.make_jaxpr(lambda q: common.cached_attention(q, *rest, **how))(chunk))
    assert tick.count("pallas_call") == int(on_tpu) and ("while" in tick) == (not on_tpu)
    assert "pallas_call" not in walk and "while" in walk
