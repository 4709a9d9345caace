"""The serving cache stores its pools positions-minor and writes a token
into the 128-position window that holds it (ISSUE 27). What is
written is what the scatter on ``[slots, positions, heads, head dim]``
wrote, bit for bit: the same values, int8 codes and scales, nothing for a
parked slot, a padded chunk overwritten by the next tokens; every reader
outside ``DecodeCache`` sees position-major rows through the
``slot_pool_*`` accessors; tokens equal the lockstep ``generate`` path's."""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler, Request,
                                             ServingConfig, slot_capacity)
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.models import common
from deepspeed_tpu.models.common import (DecodeCache, _append_in_place, _kv_quantize, init_cache,
                                         slot_pool, slot_pool_append,
                                         slot_pool_positions_touched, slot_pool_rows,
                                         slot_pool_scale)
from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils.trace import recorder

SLOTS, POSITIONS, HEADS = 5, 256, 2
#: write positions: a window's last lanes (a block of 5 straddles the
#: boundary), a parked slot, the pool's start, its last positions (tokens
#: past the extent are dropped), mid-window
WRITE_POS = np.array([126, POSITIONS, 0, POSITIONS - 2, 48], np.int32)


@pytest.fixture(autouse=True)
def _clear_topology():
    set_topology(None)
    yield
    set_topology(None)


@pytest.fixture(params=["scatter", "in_place"])
def write(request, monkeypatch):
    """The write the CPU runs, and the one a TPU runs, run here."""
    if request.param == "in_place":
        monkeypatch.setattr(common, "slot_pool_append",
                            lambda leaves, updates, pos, rows=None: _append_in_place(
                                leaves, updates, pos.astype(jnp.int32), rows))
    return request.param


class _Layer(nn.Module):
    """One attention layer's cache, nothing else."""
    head_dim: int

    @nn.compact
    def __call__(self, k, v):
        cache = DecodeCache(self, k.shape[0], POSITIONS, HEADS, self.head_dim, k.dtype)
        return cache.append(k, v, jnp.float32)


def _random(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _lockstep_pools(rng, head_dim, quant):
    """Pools that are nowhere zero, in the lockstep form [S, P, H, D] (int8
    scales [S, P, H, 1]), so a dropped write and a write are told apart."""
    shape = (SLOTS, POSITIONS, HEADS, head_dim)
    pools = {"cached_key": _random(rng, shape, jnp.int8 if quant else jnp.float32),
             "cached_value": _random(rng, shape, jnp.int8 if quant else jnp.float32)}
    if quant:
        pools["cached_key_scale"] = _random(rng, shape[:-1] + (1,), jnp.float32)
        pools["cached_value_scale"] = _random(rng, shape[:-1] + (1,), jnp.float32)
    return pools


def _stored(pools):
    """The same contents in the serving cache's stored form."""
    return {name: jnp.moveaxis(leaf[..., 0] if name.endswith("_scale") else leaf, 1, -1)
            for name, leaf in pools.items()}


def _scatter_reference(pools, k, v, pos, quant):
    """The write as it was before the stored form changed."""
    at = pos[:, None] + np.arange(k.shape[1])[None, :]
    vals = {"cached_key": k, "cached_value": v}
    if quant:
        (kq, ks), (vq, vs) = _kv_quantize(k), _kv_quantize(v)
        vals = {"cached_key": kq, "cached_value": vq,
                "cached_key_scale": ks, "cached_value_scale": vs}
    return {name: leaf.at[jnp.arange(SLOTS)[:, None], at].set(vals[name])
            for name, leaf in pools.items()}


def _assert_same(stored, lockstep):
    assert stored.keys() == lockstep.keys()
    for name, want in _stored(lockstep).items():
        assert stored[name].dtype == want.dtype and stored[name].shape == want.shape, name
        np.testing.assert_array_equal(np.asarray(stored[name]), np.asarray(want), err_msg=name)


# ---------------------------------------------------------------------------
# the write: DecodeCache against the scatter it replaced
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("length", [1, 16, 5], ids=["decode", "chunk16", "verify_straddling"])
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8kv"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_decode_cache_writes_what_the_scatter_wrote(head_dim, quant, length, write):
    rng = np.random.default_rng(head_dim + length)
    before = _lockstep_pools(rng, head_dim, quant)
    k, v = (_random(rng, (SLOTS, length, HEADS, head_dim), jnp.float32) for _ in range(2))
    cache = dict(_stored(before), cache_index=jnp.asarray(WRITE_POS))
    (keys, values, lengths), upd = _Layer(head_dim).apply({"cache": cache}, k, v, mutable=["cache"])
    after = dict(upd["cache"])
    np.testing.assert_array_equal(np.asarray(after.pop("cache_index")), WRITE_POS + length)
    np.testing.assert_array_equal(np.asarray(lengths), WRITE_POS + length)
    # ``append`` hands the whole pools on: nothing walked
    assert not np.asarray(after.pop("kv_reads")).any()
    want = _scatter_reference(before, k, v, WRITE_POS, quant)
    _assert_same(after, want)
    # the parked slot's pool is untouched
    for name, leaf in _stored(before).items():
        np.testing.assert_array_equal(np.asarray(after[name][1]), np.asarray(leaf[1]), err_msg=name)
    # attention reads [S, P, H, D], dequantised
    for got, name in ((keys, "cached_key"), (values, "cached_value")):
        full = want[name].astype(jnp.float32)
        if quant:
            full = full * want[name + "_scale"]
        assert got.shape == (SLOTS, POSITIONS, HEADS, head_dim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(full), err_msg=name)


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8kv"])
def test_padded_final_chunk_is_overwritten_by_the_next_tokens(quant, write):
    """A short final chunk is right-padded: its pad positions are written,
    then written again by the tokens that follow."""
    rng = np.random.default_rng(3)
    head_dim, chunk, real = 64, 16, 5
    before = _lockstep_pools(rng, head_dim, quant)
    layer = _Layer(head_dim)
    cache = dict(_stored(before), cache_index=jnp.asarray(WRITE_POS))
    want = before
    for pos, length in ((WRITE_POS, chunk), (np.where(WRITE_POS < POSITIONS, WRITE_POS + real,
                                                      POSITIONS).astype(np.int32), 1)):
        k, v = (_random(rng, (SLOTS, length, HEADS, head_dim), jnp.float32) for _ in range(2))
        cache["cache_index"] = jnp.asarray(pos)
        _, upd = layer.apply({"cache": cache}, k, v, mutable=["cache"])
        cache = dict(upd["cache"])
        want = _scatter_reference(want, k, v, pos, quant)
    cache.pop("cache_index")
    assert not np.asarray(cache.pop("kv_reads")).any()
    _assert_same(cache, want)


# ---------------------------------------------------------------------------
# the write alone, at the shapes and dtypes a pool leaf can have
# ---------------------------------------------------------------------------
def _written(pool, new, pos):
    """Row p of slot s holds token p - pos[s]; what falls past the extent
    is dropped."""
    ref, new = np.array(pool.astype(jnp.float32)), np.asarray(new.astype(jnp.float32))
    for s, p in enumerate(pos):
        n = max(0, min(new.shape[1], ref.shape[-1] - p))
        ref[s, ..., p:p + n] = np.moveaxis(new[s, :n], 0, -1)
    return ref


@pytest.mark.parametrize("length", [1, 16, 5, 200], ids=["decode", "chunk16", "verify_straddling",
                                                         "longer_than_a_window"])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16, jnp.float32], ids=["int8", "bf16", "fp32"])
@pytest.mark.parametrize("head_dim,heads", [(64, 2), (128, 2), (16, 3)])
def test_the_write_puts_each_token_on_its_position(head_dim, heads, dtype, length):
    rng = np.random.default_rng(length)
    pool = _random(rng, (SLOTS, heads, head_dim, POSITIONS), dtype)
    scale = _random(rng, (SLOTS, heads, POSITIONS), jnp.bfloat16)
    new = _random(rng, (SLOTS, length, heads, head_dim), dtype)
    new_scale = _random(rng, (SLOTS, length, heads), jnp.bfloat16)
    got = jax.jit(lambda *a: _append_in_place(a[:2], a[2:4], a[4]))(
        pool, scale, new, new_scale, jnp.asarray(WRITE_POS))
    twin = slot_pool_append([pool, scale], [new, new_scale], jnp.asarray(WRITE_POS))
    for g, t in zip(got, twin):   # off the TPU: the scatter
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(t.astype(jnp.float32)))
    for g, leaf, upd in zip(got, (pool, scale), (new, new_scale)):
        assert g.dtype == leaf.dtype and g.shape == leaf.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      _written(leaf, upd, WRITE_POS))


@pytest.mark.parametrize("length", [1, 16, 200], ids=["decode", "chunk16", "longer_than_a_window"])
@pytest.mark.parametrize("append", [_append_in_place, slot_pool_append], ids=["in_place", "scatter"])
def test_the_write_of_a_few_sequences_lands_in_their_slots_rows(append, length):
    """``rows``: the updates are a few sequences', each a slot of its own
    (a rung of the prefill program, ISSUE 33): each lands at its position in
    its slot's row, a parked one nowhere, and no other row is touched."""
    rng = np.random.default_rng(length)
    pool = _random(rng, (SLOTS, 2, 64, POSITIONS), jnp.int8)
    scale = _random(rng, (SLOTS, 2, POSITIONS), jnp.bfloat16)
    rows = np.array([SLOTS - 1, 0, 2], np.int32)                 # not in order, not the first
    pos = np.array([WRITE_POS[1], POSITIONS, 120], np.int32)     # the second is parked
    new = _random(rng, (3, length, 2, 64), jnp.int8)
    new_scale = _random(rng, (3, length, 2), jnp.bfloat16)
    got = jax.jit(lambda *a: append(a[:2], a[2:4], a[4], a[5]))(
        pool, scale, new, new_scale, jnp.asarray(pos), jnp.asarray(rows))
    for g, leaf, upd in zip(got, (pool, scale), (new, new_scale)):
        # the same sequences written where a whole batch would carry them
        whole_pos = np.full(SLOTS, POSITIONS, np.int32)
        whole = np.zeros((SLOTS,) + upd.shape[1:], np.float32)
        whole_pos[rows], whole[rows] = pos, np.asarray(upd.astype(jnp.float32))
        assert g.dtype == leaf.dtype and g.shape == leaf.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      _written(leaf, jnp.asarray(whole).astype(leaf.dtype), whole_pos))


def test_a_pool_shorter_than_a_lane_row_is_one_window():
    rng = np.random.default_rng(0)
    pool = _random(rng, (3, 2, 8, 48), jnp.int8)
    new = _random(rng, (3, 4, 2, 8), jnp.int8)
    pos = np.array([46, 48, 0], np.int32)
    for append in (_append_in_place, slot_pool_append):
        got, = append([pool], [new], jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(got), _written(pool, new, pos))


def test_positions_touched_counts_what_the_write_rewrites(monkeypatch):
    # the scatter rewrites what it is handed, less what falls off the pool
    assert slot_pool_positions_touched(WRITE_POS, 1, POSITIONS) == 4
    assert slot_pool_positions_touched(WRITE_POS, 5, POSITIONS) == 5 + 5 + 2 + 5
    # the in-place write: one window a live slot for one token, two for
    # more (the piece may straddle), a piece a window's worth of tokens; a
    # parked slot touches nothing; a pool shorter than a lane row is one
    # window
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert slot_pool_positions_touched(WRITE_POS, 1, POSITIONS) == 4 * 128
    assert slot_pool_positions_touched(WRITE_POS, 5, POSITIONS) == 4 * 256
    assert slot_pool_positions_touched(WRITE_POS, 200, 1024) == 5 * 2 * 256
    assert slot_pool_positions_touched(np.array([POSITIONS]), 16, POSITIONS) == 0
    assert slot_pool_positions_touched(np.array([3, 40]), 8, 48) == 2 * 48


# ---------------------------------------------------------------------------
# which code writes on a TPU: the kernel wherever its shapes allow, over one
# part too, and the loop for what it refuses (ISSUE 56)
# ---------------------------------------------------------------------------
PLACES = 512
#: a window's last lanes (5 tokens straddle the boundary), a parked slot, the
#: pool's start, its last positions (tokens over the extent's end are dropped),
#: mid-window
KERNEL_POS = np.array([126, PLACES, 0, PLACES - 2, 300], np.int32)
KINDS = ["gpt2_int8", "bf16", "fp32", "latent", "latent_3d"]


def _leaves(kind, rng, places, n, length, heads=16, head_dim=64):
    """``(leaves, updates)`` of one layer's write: the stored leaves of
    ``SLOTS`` slots and ``n`` sequences' ``length`` tokens."""
    if kind == "gpt2_int8":     # int8 codes with bfloat16 scales, K and V
        rows, dtypes = [(heads, head_dim)] * 2 + [(heads,)] * 2, [jnp.int8] * 2 + [jnp.bfloat16] * 2
    elif kind in ("bf16", "fp32"):
        rows, dtypes = [(heads, head_dim)] * 2, [jnp.bfloat16 if kind == "bf16" else jnp.float32] * 2
    else:                       # one latent a position, as ``LatentCache`` stores it or flat
        rows, dtypes = [(1, 192) if kind == "latent" else (192,)], [jnp.bfloat16]
    return ([_random(rng, (SLOTS,) + row + (places,), dtype) for row, dtype in zip(rows, dtypes)],
            [_random(rng, (n, length) + row, dtype) for row, dtype in zip(rows, dtypes)])


def _pieces(fn):
    """``fn()`` and the pieces it sent down each path: (the kernel, the loop)."""
    names = ("kv_write_kernel_pieces", "kv_write_loop_pieces")
    before = [recorder().counters.get(name, 0) for name in names]
    out = fn()
    return out, tuple(recorder().counters.get(name, 0) - b for name, b in zip(names, before))


def _assert_equal(got, want, leaves):
    assert len(got) == len(want) == len(leaves)
    for g, w, leaf in zip(got, want, leaves):
        assert g.dtype == leaf.dtype and g.shape == leaf.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


@pytest.mark.parametrize("rung", [False, True], ids=["every_slot", "a_rung_out_of_order"])
@pytest.mark.parametrize("length", [1, 5, 128, 200], ids=["a_token", "straddling", "a_window",
                                                          "two_pieces"])
@pytest.mark.parametrize("kind", KINDS)
def test_the_kernel_over_one_part_writes_what_the_scatter_writes(kind, length, rung):
    """What a TPU runs for every serving family's shapes, run here by the
    interpreter: every piece through ``ops/pallas/pool_write.py``, none
    through the loop, bit for bit the scatter's pools."""
    rng = np.random.default_rng(length)
    rows = jnp.asarray([SLOTS - 1, 0, 2], jnp.int32) if rung else None
    pos = KERNEL_POS[[0, 1, 3]] if rung else KERNEL_POS
    leaves, updates = _leaves(kind, rng, PLACES, len(pos), length)
    got, pieces = _pieces(lambda: _append_in_place(leaves, updates, jnp.asarray(pos), rows))
    assert pieces == (-(-length // 128), 0)
    _assert_equal(got, slot_pool_append(leaves, updates, jnp.asarray(pos), rows), leaves)
    # the parked sequence's slot, and the slots no sequence names, as they were
    for slot in ([1] if rows is None else [0, 1, 3]):
        for g, leaf in zip(got, leaves):
            np.testing.assert_array_equal(np.asarray(g[slot].astype(jnp.float32)),
                                          np.asarray(leaf[slot].astype(jnp.float32)))
    assert any((np.asarray(g.astype(jnp.float32)) != np.asarray(leaf.astype(jnp.float32))).any()
               for g, leaf in zip(got, leaves))


@pytest.mark.parametrize("rung", [False, True], ids=["every_slot", "a_rung_out_of_order"])
@pytest.mark.parametrize("length", [1, 5, 128, 200], ids=["a_token", "straddling", "a_window",
                                                          "two_pieces"])
@pytest.mark.parametrize("kind", ["gpt2_int8", "latent"])
def test_the_ring_write_down_the_tpus_path_wraps_as_the_scatter_does(kind, length, rung, monkeypatch):
    """``ring_pool_append`` as a TPU runs it (two in-place writes, the second a
    ring earlier) against the scatter modulo the ring: a ring of six windows
    (Laguna's and dots3's 768 places), a piece across the ring's wrap, one
    several turns on, and a slot that is not ``live``."""
    rng = np.random.default_rng(length)
    ring = 768
    pos = np.array([ring - 3, 5, 3 * ring + 700, 2 * ring + 126, ring + 300], np.int32)
    live = np.array([True, False, True, True, True])
    rows = None
    if rung:
        rows, pos, live = jnp.asarray([SLOTS - 1, 0, 2], jnp.int32), pos[:3], live[:3]
    leaves, updates = _leaves(kind, rng, ring, len(pos), length, heads=8, head_dim=128)
    want = common.ring_pool_append(leaves, updates, jnp.asarray(pos), jnp.asarray(live), rows)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "interpret_default", lambda: True)
    got, pieces = _pieces(lambda: common.ring_pool_append(
        leaves, updates, jnp.asarray(pos), jnp.asarray(live), rows))
    assert pieces == ((1 if length == 1 else 2) * -(-length // 128), 0)
    _assert_equal(got, want, leaves)
    dead = 1 if rows is None else 0
    for g, leaf in zip(got, leaves):
        np.testing.assert_array_equal(np.asarray(g[dead].astype(jnp.float32)),
                                      np.asarray(leaf[dead].astype(jnp.float32)))


@pytest.mark.parametrize("parts", [1, 2], ids=["one_part", "a_pass_of_two"])
@pytest.mark.parametrize("length", [1, 5, 200], ids=["a_token", "straddling", "two_pieces"])
@pytest.mark.parametrize("refused, places, heads", [("a_short_pool", 48, 2), ("gpt2_xl", 256, 25),
                                                    ("odd_heads_bf16_scales", 256, 3)])
def test_the_shapes_the_kernel_refuses_go_through_the_loop(refused, places, heads, length, parts):
    """A pool shorter than a lane row and odd head counts under bfloat16 scales
    (GPT-2 XL's 25): ``takes`` says no, the slots' loop writes them, a looped
    stack's pass too, and what is written is the scatter's."""
    from deepspeed_tpu.ops.pallas import pool_write
    rng = np.random.default_rng(length)
    pos = np.array([places - 2, places, 0, 7, 30], np.int32)
    stored, updates = _leaves("gpt2_int8", rng, places, SLOTS, length, heads=heads, head_dim=16)
    leaves = [jnp.concatenate([leaf] * parts, axis=1) for leaf in stored]
    assert not pool_write.takes(leaves, [u[:, :128] for u in updates])
    for part in range(parts):
        how = {} if parts == 1 else {"part": jnp.int32(part), "parts": parts}
        got, pieces = _pieces(lambda: _append_in_place(leaves, updates, jnp.asarray(pos), **how))
        assert pieces == (0, -(-length // min(places, 128)))
        _assert_equal(got, slot_pool_append(leaves, updates, jnp.asarray(pos), **how), leaves)


def test_a_ring_of_one_window_takes_a_token_through_the_kernel_and_a_piece_through_the_loop():
    """The choice is a piece's: 128 places hold a token's window and not a
    piece's two."""
    rng = np.random.default_rng(0)
    pos = jnp.asarray([126, 128, 0, 127, 64], jnp.int32)
    for length, pieces in ((1, (1, 0)), (5, (0, 1))):
        leaves, updates = _leaves("gpt2_int8", rng, 128, SLOTS, length, heads=2)
        got, took = _pieces(lambda: _append_in_place(leaves, updates, pos))
        assert took == pieces
        _assert_equal(got, slot_pool_append(leaves, updates, pos), leaves)


# ---------------------------------------------------------------------------
# readers outside DecodeCache: prefix publish and restore, capacity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module", params=[64, 128], ids=["head64", "head128"])
def engine_cfg(request):
    set_topology(None)
    cfg = get_gpt2_config("test", n_layer=2, n_positions=POSITIONS, n_head=HEADS,
                          n_embd=HEADS * request.param)
    topo = MeshTopology(tensor=1, data=1, fsdp=1, devices=jax.devices()[:1])
    engine = InferenceEngine(GPT2LMHeadModel(cfg),
                             DeepSpeedInferenceConfig(replace_with_kernel_inject=False),
                             topology=topo)
    yield engine, cfg
    set_topology(None)


def _lockstep_kv(engine, prompt):
    """The prompt's K and V as the lockstep decode path caches them."""
    cache = init_cache(engine.module, 1)
    _, upd = engine.module.apply({"params": engine.params, "cache": cache},
                                 jnp.asarray(prompt)[None], decode=True, mutable=["cache"])
    return {jax.tree_util.keystr(path): np.asarray(leaf[0, :len(prompt)])
            for path, leaf in jax.tree_util.tree_flatten_with_path(upd["cache"])[0]
            if leaf.ndim == 4}


@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8kv"])
def test_published_prefix_rows_are_the_tokens_kv_and_restore_bit_exact(engine_cfg, kv_quant):
    engine, cfg = engine_cfg
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=2, prefill_chunk=16, page_size=16, kv_quant=kv_quant, prefix_cache="on"))
    assert sched.capacity == slot_capacity(sched._cache) == POSITIONS
    # 150 tokens: the prompt's rows cross the first window's boundary
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, 150).astype(np.int32)
    first = Request(prompt=prompt, max_new_tokens=4)
    sched.submit(first)
    sched.run_until_drained(max_ticks=200)
    want = _lockstep_kv(engine, prompt)

    # a second request with the same prompt restores the published blocks
    # into a slot and skips their prefill
    again = Request(prompt=prompt, max_new_tokens=4)
    sched.submit(again)
    sched.step()
    slot = sched._slot_req.index(again)
    cached = again.cached_prefix_tokens
    assert cached >= 144 and cached % 16 == 0
    rows = sched._kv_rows(sched._cache, slot, 0, cached)
    leaves = sched._kv_slot_leaves(sched._cache, slot, cached)
    assert rows.keys() == leaves.keys() and len(rows) == (8 if kv_quant else 4)
    for key, got in rows.items():
        np.testing.assert_array_equal(got, leaves[key])
        assert got.flags["C_CONTIGUOUS"] and got.base is None  # a copy, not a view of the pool
        if key.endswith("_scale']"):
            assert got.shape == (cached, HEADS)
            continue
        assert got.shape == (cached, HEADS, cfg.head_dim)
        if kv_quant:
            got = got * rows[key[:-2] + "_scale']"][..., None].astype(np.float32)
        # chunked prefill against one pass: the same K and V up to rounding
        np.testing.assert_allclose(got, want[key][:cached], atol=0.03 if kv_quant else 1e-4,
                                   err_msg=key)
    sched.run_until_drained(max_ticks=200)
    assert again.output == first.output

    # migration's writer puts the rows back where its reader found them
    other = 1 - slot
    sched._cache = sched._restore_slot_kv(sched._cache, other, rows, cached)
    for key, got in sched._kv_rows(sched._cache, other, 0, cached).items():
        np.testing.assert_array_equal(got, rows[key], err_msg=key)


def test_stored_form_helpers_agree():
    leaf = jnp.zeros((3, 32, 2, 8), jnp.bfloat16)
    stored = slot_pool(leaf)
    assert stored.shape == (3, 2, 8, 32) and stored.dtype == leaf.dtype
    assert slot_pool_scale(stored).shape == (3, 2, 32)
    host = np.arange(3 * 2 * 8 * 32, dtype=np.float32).reshape(3, 2, 8, 32)
    rows = slot_pool_rows(host, 1, 4, 9)
    assert rows.shape == (5, 2, 8)
    np.testing.assert_array_equal(rows[2], host[1, :, :, 6])


# ---------------------------------------------------------------------------
# end to end: chunked prefill + decode over the stored form equal generate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("slots", [4, 32], ids=["whole", "rung"])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8kv"])
def test_scheduler_tokens_equal_generate_across_a_window_boundary(engine_cfg, kv_quant, write,
                                                                  slots, monkeypatch):
    """Chunked prefill, then decode ticks that read the stored pool where it
    lies (``DecodeCache.attend_tick``): the whole program at 4 slots, the
    8-row rung of 32 (``programs.decode_rungs``) for the same four requests."""
    engine, cfg = engine_cfg
    # the engine keeps its serving programs: none traced under the other write
    monkeypatch.setattr(engine, "_serve_cache", {}, raising=False)
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=slots, prefill_chunk=16, kv_quant=kv_quant, prefix_cache="off"))
    assert len(sched._decode_rungs) == (2 if slots == 32 else 1)
    rng = np.random.default_rng(5)
    # prompts end on either side of position 128; decoding carries two of
    # them over it one token at a time
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in (121, 126, 140, 9)]
    reqs = [Request(prompt=p, max_new_tokens=8) for p in prompts]
    for r in reqs:
        sched.submit(r)
    sched.run_until_drained(max_ticks=400)
    for r, p in zip(reqs, prompts):
        ref = np.asarray(engine.generate(p[None], max_new_tokens=8, do_sample=False))[0, len(p):]
        if kv_quant:
            # int8 KV rounds: the first token, from the fp prompt pass, is exact
            assert r.output[0] == ref[0]
        else:
            assert list(r.output) == list(ref)
