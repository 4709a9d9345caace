"""Shared model-zoo helpers."""

import functools
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

import flax.linen as nn


def is_seq2seq_module(model: nn.Module) -> bool:
    """True when the module's __call__ takes decoder_input_ids (encoder-
    decoder models such as T5) — shared probe for init_cache and the
    inference engine so the two can never disagree."""
    import inspect
    try:
        return "decoder_input_ids" in inspect.signature(type(model).__call__).parameters
    except (TypeError, ValueError):
        return False


def init_cache(model: nn.Module, batch_size: int, rng=None):
    """Build a zeroed decode cache for any model supporting ``decode=True``
    (the reference's ``allocate_workspace`` KV-cache setup,
    ``csrc/transformer/inference/csrc/pt_binding.cpp:1928``).

    Uses ``eval_shape`` so no compute runs and the cache index starts at 0
    (``model.init(decode=True)`` would advance it by tracing the call body).
    """
    ids = jnp.zeros((batch_size, 1), jnp.int32)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    kwargs = {"decoder_input_ids": ids} if is_seq2seq_module(model) else {}
    shapes = jax.eval_shape(lambda: model.init(rng, ids, decode=True, **kwargs))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])


#: names of the decode cache's leaves, for whoever walks a cache without the
#: model (``inference/serving``): the pools of :class:`DecodeCache` (an int8
#: pool has a ``<name>_scale`` beside it), and the leaves that hold write
#: positions: each layer's ``cache_index``, and the ``position_index`` a
#: model with a learned position table (GPT-2) keeps at its top level
KV_LEAVES = ("cached_key", "cached_value")
INDEX_LEAVES = ("cache_index", "position_index")
#: a third kind of per-position pool (:class:`LatentCache`, ``models/deepseek_v3.py``):
#: ONE pool a layer that all the heads share, the compressed latent and the
#: rotated rope key of a position, with no value pool beside it. In the stored
#: form it is a pool of one "head" as wide as the latent, so whoever carries a
#: slot's rows by position (prefix blocks, a migrated slot) carries its rows as
#: it carries keys and values: ``POOL_LEAVES`` is what such a walker asks for
LATENT_LEAVES = ("cached_latent",)
#: beside a latent pool, where the layer picks the positions it attends
#: (``models/deepseek_v3.py``, an indexed layer): the indexer's one key a
#: position, a pool of one "head" in the same stored form over the same
#: positions, so the walkers carry its rows as they carry the latent's
INDEX_KEY_LEAVES = ("cached_index_key",)
POOL_LEAVES = KV_LEAVES + LATENT_LEAVES + INDEX_KEY_LEAVES
#: a window layer's latent (:class:`LatentCache` with ``ring=True``): the
#: stored form of a pool, but its extent is a RING, the window and one chunk,
#: written at ``position mod ring``: position ``p`` of a slot is there only
#: while the slot's length is under ``p + ring``. It is no pool: it has no row
#: for most positions, so whoever carries rows by position (prefix blocks,
#: migration, a speculation's drafter) refuses a cache that has one, its
#: extent is not the slot's capacity, and a parked slot's sentinel position
#: must not be folded into it (``fed`` 0 is what drops a ring's write)
#: a grouped-query window layer (``models/llama.py``) keeps the same kind of
#: ring for its keys and its values, ``RING_KV_LEAVES`` (:class:`DecodeCache`
#: with ``ring=True``): these two are quantised as a pool's keys and values are
#: (``<name>_scale`` beside each), the latent's ring is not
RING_KV_LEAVES = ("cached_window_key", "cached_window_value")
RING_LEAVES = ("cached_window_latent",) + RING_KV_LEAVES
#: a second kind of per-slot state, with no positions (``models/nemotron_h.py``):
#: a recurrent layer's state ``[slots, ...]``, carried from tick to tick, never
#: quantised, zeroed when a request joins at position 0; ``LENGTH_LEAVES`` say
#: how many of the tokens a slot is handed this call are real (a recurrence
#: must not advance over a chunk's padding, nor a parked slot at all), and
#: ``COUNTER_LEAVES`` are int32 counts a layer leaves for the host, which a
#: serving program sums over the layers, name by name, and returns beside its
#: tokens (``moe_rows``: an expert layer that holds a share; ``latent_reads``:
#: a latent-attention layer, positions read, positions live, bytes written;
#: ``sparse_reads``: a layer that selects or windows what it attends,
#: :data:`SPARSE_READS` names its entries; ``moe_group_rows``: a held expert
#: layer under group-limited routing, the real rows whose kept groups reach an
#: expert held here and the real rows routed)
STATE_LEAVES = ("ssm_state", "conv_state")
LENGTH_LEAVES = ("chunk_length",)
COUNTER_LEAVES = ("moe_rows", "latent_reads", "sparse_reads", "kv_reads", "kv_pass_reads",
                  "moe_group_rows")
#: the entries of a ``sparse_reads`` leaf, in order, under the names the
#: host counts them by (by the kind of tick, but for the bytes ``_written``).
#: An indexed layer fills the ``dsa_`` ones (positions of the index-key pool its
#: scores were bounded to; over its real queries, the positions each attended
#: and the positions each could have, its own included; the scores its
#: selection was bounded to, rows times positions); a window layer the
#: ``swa_`` ones (positions of the ring its attention read; of those, the ones
#: inside some real query's window)
SPARSE_READS = ("dsa_index_keys_read", "dsa_positions_selected", "dsa_positions_live",
                "swa_ring_positions_read", "swa_ring_positions_live", "dsa_latent_bytes_written",
                "dsa_index_key_bytes_written", "swa_ring_bytes_written",
                "dsa_select_positions_read")
#: the entries of a ``kv_reads`` leaf, in order (every :class:`DecodeCache`
#: carries one, zeros unless the call walked its stored pool: a serving decode
#: tick's read, :meth:`DecodeCache.attend_tick`, and ``models/llama.py``'s
#: ``_walk``): positions of a full layer's pool the walk was bounded to and, of
#: those, the ones at or before some real query; the same of a window layer's
#: ring (read; inside a real query's window); bytes a window layer wrote into
#: its ring
KV_READS = ("kv_full_positions_read", "kv_full_positions_live", "kv_ring_positions_read",
            "kv_ring_positions_live", "kv_ring_bytes_written")
#: a layer that runs several times a token over one set of weights (a looped
#: stack, ``models/llama.py`` ``loop_passes``) keeps a cache a PASS, the passes
#: side by side on its pools' head axis (:class:`DecodeCache` ``parts``), and
#: leaves beside ``kv_reads``, which it sums over its passes, the same walk's
#: pair pass by pass: ``kv_pass_reads`` [2 x passes] int32, positions read and
#: positions live of pass 0, of pass 1, ...
PASS_READS = ("kv_full_positions_read", "kv_full_positions_live")
#: a serving program that runs fewer sequences than the cache has slots (a
#: rung of ``serving/programs.py``'s prefill ladder) says which slot each
#: sequence is: ``cache_slots`` [n] int32, distinct, which the program lays
#: beside every key and value pool for the length of one call.
#: :class:`DecodeCache` then writes, and reads, row ``cache_slots[s]`` of its
#: pools for sequence ``s``. Absent (every other caller), sequence ``s`` is
#: slot ``s`` and nothing is indexed. (A latent pool has no such program:
#: ``serving/programs.py`` ``prefill_rungs``.)
SLOT_LEAF = "cache_slots"


def _cache_slots(module: nn.Module):
    """The ``cache_slots`` a serving program laid in ``module``'s cache, or None."""
    return (module.get_variable("cache", SLOT_LEAF)
            if module.has_variable("cache", SLOT_LEAF) else None)


class DecodeCache:
    """One attention layer's decode cache in the flax ``cache`` collection
    (the reference's inference workspace, ``inference_context.h``): static
    pools ``cached_key`` / ``cached_value`` and the write index
    ``cache_index``. The one implementation every decoder-only family's
    attention calls, so a change to the serving cache is made once.

    What the *provided* cache looks like decides, statically, which branch
    traces:

    * ``cache_index`` a scalar: lockstep decode (``generate``): pools
      [batch, positions, kv heads, head dim], every sequence appends at
      the same position, one ``dynamic_update_slice``.
    * ``cache_index`` a ``[batch]`` vector (``serving.make_slot_cache``):
      each slot of an in-flight batch appends at its own length, and the
      pools are stored positions-minor (:func:`slot_pool`): [slots, kv
      heads, head dim, positions], the layout the TPU holds a pool in and
      attention reads it in, so the write (:func:`slot_pool_append`)
      rewrites one or two 128-position windows per slot in place instead
      of relaying the whole pool around a scatter. Join and leave are
      positional: a parked slot's sentinel position (>= the pool's
      extent) writes nothing.
    * int8 pools (``make_slot_cache(kv_quant=True)``, the serving
      default): codes plus per-(slot, head, position) ``_scale`` leaves
      [slots, kv heads, positions], quantized on write and dequantized on
      read.

    Which read a call takes is decided by what it is, too. **One query a
    sequence over a per-slot cache** (:attr:`ticks`: a serving decode tick,
    whole or a rung) reads the pool where it lies (:meth:`attend_tick`: the
    write, then :func:`cached_attention` over :meth:`stored`, each slot as far
    as it goes). Every other call (a chunk, lockstep ``generate``) takes
    :meth:`append`'s whole pools to the attention backend its model names.
    Either leaves ``kv_reads`` (:data:`KV_READS`; zeros where nothing walked).

    ``ring=True`` (a window layer's, ``RING_KV_LEAVES``): ``positions`` is a
    RING, token ``p`` written at ``p mod positions`` (:func:`ring_pool_append`)
    and only by a sequence that is ``live`` (:meth:`write`): a parked slot's
    sentinel must not be folded into the ring. Whoever reads a ring reads the
    stored leaves (:meth:`stored`) under a mask made from the query's own
    position (:func:`ring_mask`).

    ``parts`` > 1 (a layer that a looped stack applies ``parts`` times a token):
    ONE module, one set of leaves, and a cache a pass: the pools hold ``parts``
    x ``kv_heads`` heads, and the call that is pass ``part`` (a traced scalar)
    writes and reads heads ``[part * kv_heads, (part + 1) * kv_heads)`` alone,
    where they lie: no pass sees another's keys. Every pass of a token writes
    at the same position, so the index moves with the LAST pass only, and
    ``kv_reads`` is the sum over the passes (``kv_pass_reads`` has each pass's
    pair). The slot axis stays first and positions last, so whoever walks a
    serving cache by its leaves' names sees pools of more heads and nothing new.
    """

    def __init__(self, module: nn.Module, batch: int, positions: int, kv_heads: int,
                 head_dim: int, dtype, ring: bool = False, parts: int = 1, part=None):
        if parts > 1 and ring:
            raise NotImplementedError("a ring a pass (a window layer of a looped stack): not built")
        shape = (batch, positions, kv_heads * parts, head_dim)
        key, value = RING_KV_LEAVES if ring else KV_LEAVES
        self.key = module.variable("cache", key, jnp.zeros, shape, dtype)
        self.value = module.variable("cache", value, jnp.zeros, shape, dtype)
        self.quantized = self.key.value.dtype == jnp.int8
        if self.quantized:
            self.key_scale = module.variable("cache", key + "_scale", jnp.zeros,
                                             (batch, kv_heads * parts, positions), dtype)
            self.value_scale = module.variable("cache", value + "_scale", jnp.zeros,
                                               (batch, kv_heads * parts, positions), dtype)
        self.index = module.variable("cache", "cache_index", lambda: jnp.zeros([], jnp.int32))
        self.slots = _cache_slots(module)
        self.ring = ring
        self.parts = parts
        self.part = None if parts == 1 else jnp.asarray(0 if part is None else part, jnp.int32)
        if parts > 1:
            self.pass_reads = module.variable("cache", "kv_pass_reads", jnp.zeros,
                                              (len(PASS_READS) * parts,), jnp.int32)
        # for the host, beside a serving tick's tokens; every call says anew what
        # it walked (a server's programs share one cache tree, and a lockstep
        # cache carries the leaf too: a cache's leaves are the same whoever made it)
        self.reads = module.variable("cache", "kv_reads", jnp.zeros, (len(KV_READS),), jnp.int32)
        self.count_reads()

    @property
    def per_slot(self) -> bool:
        return self.index.value.ndim > 0

    def ticks(self, length: int) -> bool:
        """Whether a call of ``length`` tokens a sequence is a serving decode
        tick, which reads its pool through :meth:`attend_tick`."""
        return self.per_slot and length == 1

    def count_reads(self, **counts):
        """Leave ``counts`` (of :data:`KV_READS`; the rest 0) in ``kv_reads``:
        of a pass after the first, added to what the passes before it left."""
        new = jnp.stack([jnp.asarray(counts.get(name, 0), jnp.int32) for name in KV_READS])
        if self.parts == 1:
            self.reads.value = new
            return
        first = self.part == 0
        self.reads.value = jnp.where(first, 0, self.reads.value) + new
        pair = jnp.stack([jnp.asarray(counts.get(name, 0), jnp.int32) for name in PASS_READS])
        self.pass_reads.value = jax.lax.dynamic_update_slice(
            jnp.where(first, 0, self.pass_reads.value), pair, (self.part * len(PASS_READS),))

    def _advance(self, length: int):
        """Move the index over ``length`` written tokens: every pass of a
        token writes at the same position, so only the last pass moves it."""
        step = length if self.parts == 1 else jnp.where(self.part == self.parts - 1, length, 0)
        self.index.value = self.index.value + step

    def attend_tick(self, q, k, v, block: Optional[int] = None, q_pos=None, fed=None):
        """A serving decode tick's attention: ``k`` / ``v`` [batch, 1, kv
        heads, head dim] written at the index, then ``q`` [batch, 1, heads,
        head dim] against each sequence's pool as it is stored
        (:func:`cached_attention`: grouped-query, int8 codes as they lie, a
        slot read as far as it goes; on a TPU ``ops/pallas/pool_decode.py``),
        under the causal mask of ``q_pos`` [batch] (None: where it wrote).
        A sequence parked at or past the pool's extent reads nothing and gives
        zeros, as does one whose ``fed`` [batch] is 0. ``block``: key positions
        a step (None: :func:`decode_key_block`). Returns ``out`` like ``q``."""
        places = self.key.value.shape[-1]
        kv_heads, head_dim = k.shape[2:]
        at = self.write(k, v)
        live = at < places
        if fed is not None:
            live &= fed > 0
        q_pos = at if q_pos is None else q_pos
        out, read = cached_attention(
            q, *self.stored(), q_pos[:, None], live.astype(jnp.int32), window=places,
            block=block or decode_key_block(kv_heads, head_dim, places), rows=self.slots,
            **self._part)
        self.count_reads(kv_full_positions_read=read, kv_full_positions_live=jnp.where(
            live, jnp.minimum(q_pos + 1, places), 0).sum())
        return out

    @property
    def _part(self) -> dict:
        """What tells a walker of the stored leaves which pass's heads to take."""
        return {} if self.parts == 1 else {"part": self.part, "parts": self.parts}

    def positions(self, length: int):
        """[batch, length] positions of the ``length`` tokens about to be
        appended (what RoPE rotates by)."""
        idx = self.index.value
        start = idx[:, None] if self.per_slot else idx
        batch = idx.shape[0] if self.per_slot else self.key.value.shape[0]
        return jnp.broadcast_to(start + jnp.arange(length)[None, :], (batch, length))

    def append(self, k, v, read_dtype):
        """Write ``k`` / ``v`` [batch, l, kv heads, head dim] at the index,
        advance it, and return ``(keys, values, decode_lengths)``: the
        sequences' pools [batch, positions, kv heads, head dim] as attention
        reads them (``read_dtype`` values, HBM holds the codes; with
        ``cache_slots`` the rows of those slots alone, so a call over a few
        slots dequantises and attends a few) and each sequence's live
        length."""
        b, l = k.shape[0], k.shape[1]
        if self.ring:
            raise NotImplementedError("a ring is read as it is stored, under ring_mask: "
                                      "write() and stored()")
        idx = self.index.value
        self.write(k, v)
        if self.per_slot:
            scales = (self.key_scale, self.value_scale) if self.quantized else (None, None)
            keys, values = (slot_pool_read(pool.value, scale and scale.value, read_dtype,
                                           self.slots, **self._part)
                            for pool, scale in zip((self.key, self.value), scales))
            return keys, values, idx + l
        # per-sequence live lengths: the flash backend's decode kernel
        # skips dead KV blocks, the XLA backend masks by them
        keys, values = self.key.value, self.value.value
        if self.parts > 1:
            keys, values = (_heads_of_part(t, 2, self.part, self.parts) for t in (keys, values))
        return keys, values, jnp.broadcast_to(idx + l, (b,))

    def _append_per_slot(self, k, v, live=None):
        pools, vals = [self.key, self.value], [k, v]
        if self.quantized:
            (k, k_s), (v, v_s) = _kv_quantize(k), _kv_quantize(v)
            pools += [self.key_scale, self.value_scale]
            vals = [k, v, k_s[..., 0], v_s[..., 0]]
        held, at = [p.value for p in pools], self.index.value
        if self.ring:
            live = jnp.ones(at.shape, bool) if live is None else live
            new = ring_pool_append(held, vals, at, live, self.slots)
        else:
            new = slot_pool_append(held, vals, at, self.slots, **self._part)
        for pool, leaf in zip(pools, new):
            pool.value = leaf

    def write(self, k, v, live=None):
        """Write ``k`` / ``v`` [batch, l, kv heads, head dim] at the index and
        advance it; returns each sequence's first written position [batch].
        ``live`` [batch] bool: the sequences that write into a ring at all (a
        pool's parked slot writes out of bounds). What was written is read
        through :meth:`stored`."""
        b, l = k.shape[:2]
        idx = self.index.value
        if self.per_slot:
            self._append_per_slot(k, v, live)
            self._advance(l)
            return idx
        self._advance(l)
        if self.quantized:
            raise NotImplementedError(
                "int8 KV pools are a per-slot serving cache "
                "(make_slot_cache(kv_quant=True)); lockstep decode uses fp KV")
        for pool, new in ((self.key, k), (self.value, v)):
            if self.ring:
                at = (idx + jnp.arange(l)) % pool.value.shape[1]
                pool.value = pool.value.at[:, at].set(new.astype(pool.value.dtype))
            else:
                first = 0 if self.parts == 1 else self.part * k.shape[2]
                pool.value = jax.lax.dynamic_update_slice(pool.value, new.astype(pool.value.dtype),
                                                          (0, idx, first, 0))
        return jnp.broadcast_to(idx, (b,))

    def stored(self):
        """``(keys, key scales, values, value scales)`` as the serving cache
        stores them: pools [slots, kv heads, head dim, positions] (the leaves
        themselves, no copy; a lockstep pool's transpose) and, of an int8
        pool, scales [slots, kv heads, positions], else None. Sequence ``s``
        of a call over ``cache_slots`` is row ``self.slots[s]``."""
        if self.per_slot:
            scales = (self.key_scale.value, self.value_scale.value) if self.quantized \
                else (None, None)
            return self.key.value, scales[0], self.value.value, scales[1]
        return (jnp.transpose(self.key.value, (0, 2, 3, 1)), None,
                jnp.transpose(self.value.value, (0, 2, 3, 1)), None)


class LatentCache:
    """One latent-attention layer's decode cache: ONE pool ``cached_latent``
    of ``width`` values a position (the normed latent and the rotated rope
    key, side by side) that every head reads, and the write index. No value
    pool: the heads' keys and values are both linear in the latent.

    As :class:`DecodeCache`, the provided cache decides the branch: a scalar
    ``cache_index`` is lockstep ``generate`` (pool [batch, positions, 1,
    width]); a ``[slots]`` vector is the serving cache, the pool stored
    positions minor-most [slots, 1, width, positions] and written in place by
    :func:`slot_pool_append`. An int8 latent is not built
    (``serving/programs.py`` ``quantize_slot_cache`` refuses it by name).

    ``name`` and ``index`` let a layer keep a second pool over the same
    positions under the same index (an indexed layer's ``cached_index_key``),
    appended with ``advance=False``. ``ring=True`` (a window layer's
    ``cached_window_latent``) makes ``positions`` a ring: token ``p`` is
    written at ``p mod positions``, and only where the sequence is ``live``
    (a parked slot's sentinel position would fold into the ring)."""

    def __init__(self, module: nn.Module, batch: int, positions: int, width: int, dtype,
                 name: str = "cached_latent", index=None, ring: bool = False):
        self.pool = module.variable("cache", name, jnp.zeros, (batch, positions, 1, width), dtype)
        self.index = index if index is not None else module.variable(
            "cache", "cache_index", lambda: jnp.zeros([], jnp.int32))
        self.ring = ring

    @property
    def per_slot(self) -> bool:
        return self.index.value.ndim > 0

    @property
    def positions(self) -> int:
        """The pool's extent, whichever form it is stored in."""
        return self.pool.value.shape[-1 if self.per_slot else 1]

    def append(self, latent, advance: bool = True, live=None):
        """Write ``latent`` [batch, l, width] at the index and advance it;
        returns ``(pool, start)``: the whole pool as it is stored for serving,
        [batch, width, positions] (the serving pool itself, no copy; a
        lockstep pool's transpose), and each sequence's first written
        position [batch]. ``live`` [batch] bool (a ring's): the sequences
        that write at all."""
        b, l = latent.shape[:2]
        idx = self.index.value
        if advance:
            self.index.value = idx + l
        new = latent[:, :, None, :].astype(self.pool.value.dtype)
        if self.per_slot:
            if self.ring:
                live = jnp.ones((b,), bool) if live is None else live
                self.pool.value, = ring_pool_append([self.pool.value], [new], idx, live)
            else:
                self.pool.value, = slot_pool_append([self.pool.value], [new], idx)
            return self.pool.value[:, 0], idx
        if self.ring:
            at = (idx + jnp.arange(l)) % self.positions
            self.pool.value = self.pool.value.at[:, at].set(new)
        else:
            self.pool.value = jax.lax.dynamic_update_slice(self.pool.value, new, (0, idx, 0, 0))
        return jnp.transpose(self.pool.value[:, :, 0], (0, 2, 1)), jnp.broadcast_to(idx, (b,))


def slot_pool(leaf):
    """A zeroed lockstep pool leaf [slots, positions, kv heads, head dim] in
    the serving cache's stored form, positions minor-most: [slots, kv heads,
    head dim, positions]. Whoever walks a serving cache's pool leaves
    outside :class:`DecodeCache` (``inference/serving``) reads them through
    the ``slot_pool_*`` functions below, never by a dimension's number."""
    s, p, h, d = leaf.shape
    return jnp.zeros((s, h, d, p), leaf.dtype)


def _heads_of_part(leaf, axis: int, part, parts: int):
    """The heads of pass ``part`` of ``parts`` on ``axis`` of a leaf that holds
    every pass's side by side (:class:`DecodeCache` ``parts``): a copy."""
    heads = leaf.shape[axis] // parts
    return jax.lax.dynamic_slice_in_dim(leaf, part * heads, heads, axis=axis)


def slot_pool_read(pool, scale, read_dtype, rows=None, part=None, parts: int = 1):
    """A stored pool as attention's [slots, positions, kv heads, head dim]
    operand, an int8 pool dequantised by its ``scale`` (attention reads fp
    values, HBM holds the codes): a change of logical order only, which the
    compiler folds into the consumer's layout. ``rows`` [n]: those slots'
    rows alone, picked before anything is dequantised; ``part`` of ``parts``:
    that pass's heads alone."""
    if rows is not None:
        pool = slot_rows(pool, rows)
        scale = None if scale is None else slot_rows(scale, rows)
    if parts > 1:
        pool = _heads_of_part(pool, 1, part, parts)
        scale = None if scale is None else _heads_of_part(scale, 1, part, parts)
    if scale is not None:
        pool = pool.astype(read_dtype) * scale[:, :, None, :]
    return jnp.transpose(pool, (0, 3, 1, 2))


# jitted, so that a model's layers share one trace of it
@jax.jit
def slot_rows(leaf, rows):
    """Rows ``rows`` [n] of a leaf that holds a row a slot, ``[n, ...]``: a
    scalar-indexed slice a row, ``n`` being a rung's few. Not ``leaf[rows]``:
    the TPU's compiler opens a gather on the slots' axis with slices of the
    WHOLE leaf (four passes a pool, 4.2 ms of a 10.7 ms decode tick over 8 of
    the chat cell's 32 slots: ``PERF.md`` section 6, PR 42), where a slice by
    a scalar reads the row it names."""
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(leaf, rows[i], 1, axis=0)
                            for i in range(rows.shape[0])])


def slot_pool_scale(leaf):
    """The zeroed int8 scale leaf of a stored pool leaf: one scale per
    (slot, kv head, position), in the pool's own dtype."""
    return jnp.zeros(leaf.shape[:2] + leaf.shape[-1:], leaf.dtype)


def slot_pool_positions(leaf) -> int:
    """Token capacity per slot of a stored pool (or scale) leaf."""
    return int(leaf.shape[-1])


def slot_pool_rows(leaf, slot: int, start: int, stop: int):
    """Positions ``[start:stop)`` of one slot of a stored leaf (a host
    array), position-major: codes or values [n, kv heads, head dim],
    scales [n, kv heads] — what a prefix block or a migration carries.
    Always a copy."""
    return np.array(np.moveaxis(leaf[slot, ..., start:stop], -1, 0), copy=True, order="C")


def slot_pool_row_shape(leaf) -> tuple:
    """The shape of one position's row of a stored leaf."""
    return tuple(leaf.shape[1:-1])


def slot_pool_set_rows(leaf, slot, rows):
    """Traced: ``rows`` (position-major, as :func:`slot_pool_rows` gives
    them) written to positions ``[0:n)`` of ``slot`` of a stored leaf."""
    return leaf.at[slot, ..., :rows.shape[0]].set(jnp.moveaxis(rows, 0, -1))


#: positions of a written window: one lane row of the TPU's tiling
_WINDOW = 128


def _append_span(length: int, positions: int) -> int:
    """Positions of a slot that :func:`_append_in_place` rewrites for a
    piece of ``length`` tokens: the aligned window that holds one token;
    two windows for more, so that the piece may straddle a boundary; a pool
    whose extent is no multiple of a window is one window."""
    w = _WINDOW if positions % _WINDOW == 0 else positions
    return w if length == 1 else min(2 * w, positions)


def slot_pool_append(leaves, updates, pos, rows=None, part=None, parts: int = 1):
    """Write ``updates[i]`` [slots, l, ...] (token-major, as the projections
    produce them) into the stored leaves ``leaves[i]`` [slots, ..., positions]
    at positions ``pos[s] .. pos[s] + l - 1`` of each slot ``s``; returns the
    new leaves. A position at or past the extent writes nothing (a parked
    slot); tokens past the extent are dropped. ``rows`` [n] int32, distinct:
    the updates are ``n`` sequences' and sequence ``s`` is slot ``rows[s]``;
    the other slots' rows come back as they went in. ``part`` of ``parts``
    (:class:`DecodeCache` ``parts``): the leaves hold ``parts`` times the
    updates' heads and the write goes to pass ``part``'s, the others' untouched.

    On a TPU, :func:`_append_in_place`: one kernel a piece
    (``ops/pallas/pool_write.py``) wherever the shapes allow, which is every
    serving family's, and the slots' loop for the rest. Elsewhere one scatter
    on the minor dimension: the same write, and what the in-place one is
    tested against; the TPU would relay the whole pool around it (``PERF.md``,
    PR 27)."""
    from deepspeed_tpu.ops.pallas import backend
    pos = pos.astype(jnp.int32)
    if backend.on_tpu():
        return _append_in_place(leaves, updates, pos, rows, part, parts)
    slots, length = updates[0].shape[:2]
    at = pos[:, None] + jnp.arange(length)[None, :]
    which = (jnp.arange(slots) if rows is None else rows)[:, None]
    # advanced indices on the first and last axes: the indexed result is
    # [slots, l, ...], the updates' own shape; out of bounds drops
    if parts == 1:
        return [leaf.at[which, ..., at]
                .set(upd.astype(leaf.dtype)) for leaf, upd in zip(leaves, updates)]
    # the passes' heads apart, [slots, parts, heads, ..., positions]: the same
    # scatter with the pass picked beside the slot
    by_pass = [leaf.reshape(leaf.shape[:1] + (parts, -1) + leaf.shape[2:]) for leaf in leaves]
    return [apart.at[which, part, ..., at].set(upd.astype(leaf.dtype)).reshape(leaf.shape)
            for leaf, apart, upd in zip(leaves, by_pass, updates)]


def ring_pool_append(leaves, updates, pos, live, rows=None):
    """:func:`slot_pool_append` into RINGS: token ``j`` of slot ``s`` goes to
    ``(pos[s] + j) mod ring`` of the stored leaves ``[slots, ..., ring]``, and
    a slot that is not ``live`` [slots] writes nothing (``pos`` is then a
    parked slot's sentinel, which must not be folded into the ring). A piece
    that runs over the ring's end goes on at its start. ``rows`` as
    :func:`slot_pool_append`'s.

    On a TPU two in-place writes (:func:`_append_in_place`: the kernel's, or
    the loop's where a ring is a single window): one at ``pos mod ring``,
    which drops what runs past the end, and, for more than one token, one a
    ring earlier, which drops all but that (a position before 0 writes
    nothing)."""
    from deepspeed_tpu.ops.pallas import backend
    ring = leaves[0].shape[-1]
    slots, length = updates[0].shape[:2]
    pos = pos.astype(jnp.int32)
    if backend.on_tpu():
        at = jnp.where(live, pos % ring, ring)
        leaves = _append_in_place(leaves, updates, at, rows)
        if length > 1:
            leaves = _append_in_place(leaves, updates, jnp.where(live, at - ring, ring), rows)
        return leaves
    at = jnp.where(live[:, None], (pos[:, None] + jnp.arange(length)[None, :]) % ring, ring)
    return [leaf.at[(jnp.arange(slots) if rows is None else rows)[:, None], ..., at]
            .set(upd.astype(leaf.dtype)) for leaf, upd in zip(leaves, updates)]


def window_ring_positions(window: int, chunk: int, page: int = 128) -> int:
    """Positions of a window layer's ring for calls of at most ``chunk``
    tokens: the ``window - 1`` positions the chunk's first query looks back on
    and the chunk itself, rounded up to whole pages."""
    return -(-(window - 1 + chunk) // page) * page


def ring_mask(q_pos, k_at, ring: int, window: int):
    """What queries at ``q_pos`` [..., l] may read of a ring's places ``k_at``
    [n]: place ``r`` holds, for a query at ``t``, position ``t - (t - r) mod
    ring`` (whatever was written there later lies ahead of ``t``); it is read
    where that is one of the ``window`` positions ending at ``t`` and not
    before 0 (never written by this sequence: a former tenant's). [..., l, n].
    A pool that never wraps is a ring of its own extent."""
    back = (q_pos[..., None] - k_at) % ring
    return (back < window) & (q_pos[..., None] - back >= 0)


def decode_key_block(kv_heads: int, head_dim: int, places: int) -> int:
    """Key positions a step of a decode attention's walk takes where the
    configuration names none: half a MiB of a leaf's codes, in whole tiles of
    128 positions and at most the pool. What the chip read as fastest, or within
    a tenth of it, at three shapes (``PERF.md`` section 6, PR 49: 16 heads of
    64 over 1,024 positions 512, 16 of 128 over 2,048 256, 2 of 128 over 2,048
    the pool): a step's cost is its bytes and about a third of a microsecond,
    and a short step's DMA is latency, not bandwidth."""
    return min(max(2 ** 19 // (kv_heads * head_dim) // 128 * 128, 128), places)


def cached_attention(q, keys, key_scale, values, value_scale, q_pos, fed, *, window: int,
                     block: int, rows=None, part=None, parts: int = 1):
    """Grouped-query softmax attention of ``q`` [b, l, H, d] (already written)
    over the cache as it is STORED: ``keys`` / ``values`` [slots, kv heads, d,
    P] and, of int8 pools, ``key_scale`` / ``value_scale`` [slots, kv heads, P]
    (the codes go into the matmuls as they are and a position's scale
    multiplies its score and its probability: nothing is dequantised whole,
    and no key head is repeated). Query head ``h`` reads key head ``h // (H /
    kv heads)``. ``q_pos`` [b, l] are the queries' positions and ``fed`` [b]
    how many of each sequence's are real (0: a parked slot, which reads
    nothing and gives zeros); sequence ``s`` is row ``rows[s]`` of the pools
    (None: ``s``). Place ``r`` of the ``P`` is read under
    :func:`ring_mask` (``window`` positions ending at the query, of a RING of
    ``P``): a pool that never wraps is a ring of its own extent, and plain
    causal attention a window of ``P``.

    A block of ``block`` key positions a step with a running softmax, the
    steps bounded by what the sequences hold; ``block`` is that and nothing
    else (whether a call walks at all is its caller's:
    :meth:`DecodeCache.attend_tick` for every family's serving decode tick,
    ``models/llama.py`` ``_walk`` for a window layer and a configured full
    one). ONE query a sequence (a decode tick): on a TPU one kernel
    (``ops/pallas/pool_decode.py``, serving only: no VJP) that reads each
    sequence's pool as far as that sequence goes; elsewhere XLA's loop, which
    walks every sequence's pool together as far as the longest goes: the same
    numbers, and what the kernel is tested against. A chunk: XLA's loop, a
    sequence at a time as far as that sequence goes. Scores are scaled by
    ``d ** -0.5``. Returns ``(out [b, l, H, d], positions read)``: the
    positions the walk that ran was bounded to.

    ``part`` of ``parts`` (:class:`DecodeCache` ``parts``): the leaves hold
    ``parts`` passes' key heads side by side and this call reads pass
    ``part``'s alone, a block's worth at a time, where they lie."""
    b, l, heads, d = q.shape
    from deepspeed_tpu.ops.pallas import backend
    looped = {} if parts == 1 else {"part": part, "parts": parts}
    if l == 1 and backend.on_tpu():
        from deepspeed_tpu.ops.pallas.pool_decode import pool_decode
        out, read = pool_decode(q[:, 0], keys, key_scale, values, value_scale, q_pos[:, 0], fed,
                                window=window, block=block, rows=rows, **looped)
        return out[:, None], read
    kv, places = keys.shape[1] // parts, keys.shape[-1]
    rep, dtype = heads // kv, q.dtype
    block = block if places % block == 0 else places
    scale = d ** -0.5
    lowest = jnp.finfo(jnp.float32).min
    grouped = jnp.transpose(q.reshape(b, l, kv, rep, d), (0, 2, 1, 3, 4))   # [b, kv, l, rep, d]
    ends = jnp.where(fed > 0, jnp.minimum(q_pos[:, 0] + fed, places), 0)    # [b]
    steps = -(-ends // block)

    def piece(leaf, rows_, j):
        """Places ``[j * block, (j + 1) * block)`` of the rows ``rows_`` (None:
        of every row, read where it lies); of a looped stack's leaf, the
        pass's heads."""
        if parts > 1:
            at = (part * kv,) + (0,) * (leaf.ndim - 3) + (j * block,)
            size = (kv,) + leaf.shape[2:-1] + (block,)
            return jnp.concatenate([jax.lax.dynamic_slice(leaf, (r,) + at, (1,) + size)
                                    for r in (range(b) if rows_ is None else rows_)])
        if rows_ is None:
            return jax.lax.dynamic_slice_in_dim(leaf, j * block, block, axis=leaf.ndim - 1)
        return jnp.concatenate([jax.lax.dynamic_slice(
            leaf, (r,) + (0,) * (leaf.ndim - 2) + (j * block,), (1,) + leaf.shape[1:-1] + (block,))
            for r in rows_])

    def step(j, state, rows_, qs, at, real):
        """One block of the rows ``rows_`` for the queries ``qs`` [n, kv, l,
        rep, d] at ``at`` [n, l], of sequences that are ``real`` [n]."""
        m, den, acc = state
        seen = ring_mask(at, j * block + jnp.arange(block), places, window) & real[:, None, None]
        seen = seen[:, None, :, None, :]                                    # [n, 1, l, 1, block]
        s = jnp.einsum("nklrd,nkdp->nklrp", qs, piece(keys, rows_, j).astype(dtype),
                       preferred_element_type=jnp.float32) * scale
        if key_scale is not None:
            s = s * piece(key_scale, rows_, j)[:, :, None, None, :].astype(jnp.float32)
        s = jnp.where(seen, s, lowest)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        den = den * alpha + p.sum(axis=-1)
        if value_scale is not None:
            p = p * piece(value_scale, rows_, j)[:, :, None, None, :].astype(jnp.float32)
        acc = acc * alpha[..., None] + jnp.einsum(
            "nklrp,nkdp->nklrd", p.astype(dtype), piece(values, rows_, j).astype(dtype),
            preferred_element_type=jnp.float32)
        return m_new, den, acc

    def walked(n, count, rows_, qs, at, real):
        state = (jnp.full((n, kv, l, rep), lowest, jnp.float32),
                 jnp.zeros((n, kv, l, rep), jnp.float32),
                 jnp.zeros((n, kv, l, rep, d), jnp.float32))
        _, den, acc = jax.lax.fori_loop(
            0, count, lambda j, state: step(j, state, rows_, qs, at, real), state)
        return (acc / jnp.maximum(den, 1e-37)[..., None]).astype(dtype)

    if l == 1:
        # every sequence's pool together, as far as the longest goes
        count = steps.max()
        out = walked(b, count, None if rows is None else [rows[s] for s in range(b)],
                     grouped, q_pos, fed > 0)
        read = count * block * b
    else:
        def one(s, out):
            pick = lambda t: jax.lax.dynamic_slice_in_dim(t, s, 1, axis=0)  # noqa: E731
            got = walked(1, steps[s], [s if rows is None else rows[s]], pick(grouped),
                         pick(q_pos), pick(fed) > 0)
            return jax.lax.dynamic_update_slice_in_dim(out, got, s, axis=0)

        out = jax.lax.fori_loop(0, b, one, jnp.zeros(grouped.shape, dtype))
        read = (steps * block).sum()
    out = jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, l, heads, d)
    return out, read.astype(jnp.int32)


def _append_in_place(leaves, updates, pos, rows=None, part=None, parts: int = 1):
    """The write as a read-modify-write of the aligned span that holds the
    tokens, a piece of at most one window's tokens at a time (a piece touches
    at most two windows). Which code makes a piece's write is its shapes' to
    say and nobody else's: where ``ops/pallas/pool_write.py`` ``takes`` them
    (pools of whole 128-position windows that hold the span, rows that pack
    into 32-bit words: every serving family's configured shapes) one kernel a
    call, every leaf of the layer and every sequence's windows in flight at
    once; where it does not (a pool shorter than a lane row, an odd head count
    under bfloat16 scales, a ring of one window written by a piece)
    :func:`_append_piece`, the same read-modify-write one slot at a time.
    ``part`` of ``parts`` goes on to either. Counted a piece, at trace time
    and over every program a process traces: ``kv_write_kernel_pieces``,
    ``kv_write_loop_pieces``."""
    from deepspeed_tpu.ops.pallas import pool_write
    from deepspeed_tpu.utils.trace import recorder
    leaves, updates = list(leaves), list(updates)
    length, piece = updates[0].shape[1], _append_span(1, leaves[0].shape[-1])
    looped = {} if parts == 1 else {"part": part, "parts": parts}
    for start in range(0, length, piece):
        new = [u[:, start:start + piece] for u in updates]
        kernel = pool_write.takes(leaves, new)
        # both names every piece, so that a run's counters say "loop 0" and do not leave it out
        recorder().count("kv_write_kernel_pieces", int(kernel))
        recorder().count("kv_write_loop_pieces", int(not kernel))
        write = pool_write.pool_write if kernel else _append_piece
        leaves = write(leaves, new, pos + start, rows, **looped)
    return leaves


# jitted, so that a model's layers share one trace of the write
@functools.partial(jax.jit, static_argnames=("parts",))
def _append_piece(leaves, updates, pos, rows=None, part=None, parts: int = 1):
    """The write of the shapes ``ops/pallas/pool_write.py`` refuses: a
    ``fori_loop`` over the sequences of scalar-indexed ``dynamic_slice`` /
    select / ``dynamic_update_slice``, which XLA updates in place under
    donation; ``part`` of ``parts``: pass ``part``'s heads alone."""
    positions = leaves[0].shape[-1]
    slots, length = updates[0].shape[:2]
    span = _append_span(length, positions)
    # the aligned span that holds [pos, pos + length), pulled inside the
    # pool: a parked slot's is the last one, where its mask is empty
    start = jnp.clip(pos // _WINDOW * _WINDOW, 0, positions - span)

    def window(leaf, upd):
        """``upd`` as the leaf lays it out, token j of slot s on lane
        ``pos[s] - start[s] + j`` of the span."""
        upd = jnp.moveaxis(upd.astype(leaf.dtype), 1, -1)
        upd = jnp.pad(upd, [(0, 0)] * (upd.ndim - 1) + [(0, span - length)])
        return jax.vmap(lambda x, by: jnp.roll(x, by, axis=-1))(upd, pos - start)

    # built outside the loop, which then only selects and stores
    wins = [window(leaf, upd) for leaf, upd in zip(leaves, updates)]
    lane = jnp.arange(span)

    def body(s, leaves):
        j = start[s] + lane - pos[s]
        written = (j >= 0) & (j < length)
        out = []
        row = s if rows is None else rows[s]
        for leaf, win in zip(leaves, wins):
            heads = leaf.shape[1] // parts
            at = (row, 0 if parts == 1 else part * heads) + (0,) * (leaf.ndim - 3) + (start[s],)
            old = jax.lax.dynamic_slice(leaf, at, (1, heads) + leaf.shape[2:-1] + (span,))
            new = jnp.where(written, jax.lax.dynamic_index_in_dim(win, s, 0), old)
            out.append(jax.lax.dynamic_update_slice(leaf, new, at))
        return out

    return jax.lax.fori_loop(0, slots, body, leaves)


def slot_pool_positions_touched(pos, length: int, positions: int) -> int:
    """Positions :func:`slot_pool_append` rewrites in one stored leaf for
    ``length`` tokens at each of ``pos`` (a host array of write positions),
    none for a parked one: on a TPU a span for every live slot and piece
    (the kernel's windows and the loop's are the same positions), elsewhere
    the positions written. What the write costs, beside the ``length`` a slot
    it is handed."""
    from deepspeed_tpu.ops.pallas import backend
    live = np.asarray(pos)[np.asarray(pos) < positions]
    if not backend.on_tpu():
        return int(np.minimum(length, positions - live).sum())
    piece = _append_span(1, positions)
    return len(live) * sum(_append_span(min(piece, length - start), positions)
                           for start in range(0, length, piece))


def _kv_quantize(vals):
    """Per-(slot, token, head) symmetric int8 KV quantization through the
    one grouped quantizer in the repo (``ops/quantizer/core``). The
    last-axis form keeps the reduce on the (unsharded) head_dim axis, so
    a head-sharded KV write on a tensor mesh quantizes in place instead
    of all-gathering the pool. Returns (codes [b, l, h, d] int8,
    scales [b, l, h, 1] in KV dtype)."""
    from deepspeed_tpu.ops.quantizer.core import quantize_lastaxis
    codes, scale = quantize_lastaxis(vals, num_bits=8)
    return codes, scale.astype(vals.dtype)


def dense_init(scale: float = 0.02):
    return nn.initializers.normal(stddev=scale)


import contextlib
import contextvars

_constraints_disabled = contextvars.ContextVar("ds_activation_constraints_disabled",
                                               default=False)


@contextlib.contextmanager
def activation_constraints_disabled():
    """Disable ``constrain_activation`` while tracing code that runs inside
    a manual ``shard_map`` body (qcomm / 1-bit collectives): per-shard code
    already IS the sharding, and ``nn.remat`` hides the surrounding mesh
    context so the constraint cannot reliably self-detect manual axes."""
    token = _constraints_disabled.set(True)
    try:
        yield
    finally:
        _constraints_disabled.reset(token)


def constrain_activation(x, *logical_names: str):
    """Pin an activation's sharding by logical axis names (t5x-style).

    Without activation constraints GSPMD is free to re-shard the forward
    however its cost model likes; on fsdp-sharded (ZeRO-3) weights it can
    settle on replicated-batch compute with per-layer contraction
    all-reduces — per-chip wire bytes then GROW with the mesh instead of
    staying flat (the reference never faces this choice: its DP ranks
    replicate compute by construction and its partitioning is imperative,
    ``stage3.py:1099``). Constraining the residual stream to
    ``("batch", "length", ...)`` makes the batch-parallel strategy the
    only consistent one, so weights get all-gathered (flat per-chip
    payload) — the ZeRO-3 weak-scaling invariant.

    No-op when no topology is set, on a trivial mesh, or when the mesh's
    axes are manual (inside ``shard_map`` bodies, e.g. the pipeline
    engine's stage loop)."""
    from jax.sharding import NamedSharding

    from deepspeed_tpu.parallel.sharding import logical_to_mesh_spec
    from deepspeed_tpu.parallel.topology import get_topology

    if _constraints_disabled.get():
        return x
    topo = get_topology()
    if topo is None:
        return x
    mesh = topo.mesh
    if mesh.size == 1:
        return x
    try:
        # inside shard_map bodies the mesh axes are Manual — per-shard code
        # already IS the sharding; a constraint there breaks lowering.
        # (Paths that remat the model inside shard_map additionally trace
        # under activation_constraints_disabled(): remat hides this mesh
        # context, see qcomm.py/zeroone.py.)
        if any(t == jax.sharding.AxisType.Manual for t in getattr(
                jax.sharding.get_abstract_mesh(), "axis_types", ())):
            return x
    except Exception:
        pass  # probe failed: proceed to constrain — the constraint is the
        # load-bearing part (weak scaling), the probe is the edge case
    spec = logical_to_mesh_spec(logical_names)
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        # rank mismatch or incompatible mesh: leave unconstrained
        return x


def maybe_remat(block_cls, cfg, layer_idx: int, static_argnums=(), enabled=None):
    """Zoo-shared selective activation checkpointing: wrap ``block_cls`` in
    ``jax.checkpoint`` (with the config's ``remat_policy``) when remat is on
    and ``layer_idx`` hits the ``remat_every`` stride; otherwise return the
    class unchanged. ``enabled`` overrides ``cfg.remat`` for callers with
    extra conditions (e.g. llama skips remat during decode).

    Every block additionally passes through ``stream_block_params`` — a
    no-op unless a ZeRO-Infinity ``offload_param`` engine is tracing, in
    which case the block's params are h2d-streamed *inside* the remat
    region so backward re-streams per layer instead of holding every
    layer's device copy from forward to backward (reference param
    coordinator re-fetch, ``partitioned_param_coordinator.py:479``)."""
    from deepspeed_tpu.runtime.zero.param_offload import stream_block_params
    block_cls = stream_block_params(block_cls)
    enabled = getattr(cfg, "remat", False) if enabled is None else enabled
    if not enabled or layer_idx % max(getattr(cfg, "remat_every", 1), 1) != 0:
        return block_cls
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import get_remat_policy
    return nn.remat(block_cls, static_argnums=static_argnums, prevent_cse=False,
                    policy=get_remat_policy(getattr(cfg, "remat_policy", None)))


def pld_gate(module: nn.Module, branch, keep):
    """Zoo-shared Switchable-Transformer gate (PLD, arXiv:2010.13369 §3):
    keep the sublayer output with probability ``keep`` and rescale by
    1/keep so expectations match; a dropped sublayer contributes nothing.
    Returns ``(gated_branch, keep_decision)`` — the decision lets callers
    gate side outputs (e.g. a dropped MoE layer's router aux loss). The
    FLOPs are still spent under jit; the TPU benefit is regularization
    parity, which is why the engine anneals theta in-graph instead of
    re-tracing."""
    if keep is None:
        return branch, None
    b = jax.random.bernoulli(module.make_rng("pld"), keep)
    return jnp.where(b, branch / keep, jnp.zeros_like(branch)), b


def rms_norm(x, weight, eps: float, out_dtype):
    """Shared RMS-norm core (LLaMA RMSNorm, T5 LayerNorm): fp32 accumulate,
    scale, cast back."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(out_dtype)


_ONEHOT_CHUNK = 1024  # tokens per backward chunk — bounds the one-hot buffer


@functools.lru_cache(maxsize=None)
def _onehot_embed_fn(vocab: int, dtype_name: str):
    @jax.custom_vjp
    def f(wte, ids):
        return jnp.take(wte, ids, axis=0)

    def fwd(wte, ids):
        return jnp.take(wte, ids, axis=0), ids

    def bwd(ids, g):
        # chunk the token axis: a single-shot one_hot is [T, V] in the grad
        # dtype (~824 MB at T=4k, V=50k, fp32); scanning T in chunks of
        # _ONEHOT_CHUNK with a bf16 one-hot (fp32 accumulation via
        # preferred_element_type) bounds the buffer to a few tens of MB
        ids_f = ids.reshape(-1)
        g_f = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
        t = ids_f.shape[0]
        ch = _ONEHOT_CHUNK
        if t <= ch:
            onehot = jax.nn.one_hot(ids_f, vocab, dtype=jnp.bfloat16)
            gw = jax.lax.dot_general(onehot, g_f, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:
            # pad to a chunk multiple — padded rows carry zero cotangent so
            # they contribute nothing, and the memory bound holds for EVERY
            # shape (a full-T fallback would reintroduce the [T, V] spike)
            pad = (-t) % ch
            if pad:
                ids_f = jnp.concatenate([ids_f, jnp.zeros((pad,), ids_f.dtype)])
                g_f = jnp.concatenate([g_f, jnp.zeros((pad, g_f.shape[-1]), g_f.dtype)])
            def body(acc, xs):
                i_c, g_c = xs
                oh = jax.nn.one_hot(i_c, vocab, dtype=jnp.bfloat16)
                return acc + jax.lax.dot_general(oh, g_c, (((0,), (0,)), ((), ())),
                                                 preferred_element_type=jnp.float32), None

            gw, _ = jax.lax.scan(body, jnp.zeros((vocab, g_f.shape[-1]), jnp.float32),
                                 (ids_f.reshape(-1, ch), g_f.reshape(-1, ch, g_f.shape[-1])))
        return gw.astype(dtype_name), None

    f.defvjp(fwd, bwd)
    return f


def take_embed_onehot_grad(wte, ids):
    """Embedding lookup whose BACKWARD is a one-hot matmul instead of a
    scatter-add. TPU scatter lowers to a serialized per-index update; the
    [T, V] x [T, E] matmul form rides the MXU (the standard TPU trick —
    costs ~V*T*E extra FLOPs, usually a small fraction of a transformer
    step). Forward is a plain gather either way."""
    return _onehot_embed_fn(int(wte.shape[0]), jnp.dtype(wte.dtype).name)(wte, ids)


def lookup_table_view(table):
    """A gather-friendly view of an embedding table on tensor/sequence
    meshes.

    With the vocab dim sharded over ``tensor`` (logical rules), GSPMD
    partitions ``take`` by psum-ing partial gathers and leaves the output
    embed-sharded; the residual-stream constraint then needs a transition
    the partitioner cannot produce — it replicates the whole activation
    ("Involuntary full rematerialization", ``spmd_partitioner.cc:652``).
    Pinning the TABLE un-sharded for the lookup moves
    the reshard onto the parameter (an ordinary all-gather — exactly the
    ZeRO-3 gather-on-use) so the gather emits (batch, length, embed)
    directly. Skipped on tensor=sequence=1 meshes, where the default
    strategy is already transition-free and the extra constraint would
    pin the ZeRO-3 table gather into a fixed materialization."""
    from deepspeed_tpu.parallel.topology import get_topology
    topo = get_topology()
    if topo is None or (topo.tensor_parallel_size <= 1
                        and topo.sequence_parallel_size <= 1):
        return table
    return constrain_activation(table, None, None)


def embed_lookup(wte, ids, onehot_grad=None, decode: bool = False):
    """Token-embedding gather, shared across the model zoo.

    ``onehot_grad`` (None = policy default, on): backward as a one-hot einsum instead of a
    scatter-add — MXU-friendly and cleanly partitionable (the scatter's
    batch→embed update reshard is a GSPMD involuntary-remat source).
    ``decode``: per-token serving step — skip the table reshard
    (:func:`lookup_table_view`); a whole-table all-gather per generated
    token would dwarf the [B,1,E] gather it optimizes, and the decode
    gather's output transition is negligible at one token."""
    if onehot_grad is None:
        onehot_grad = True  # the one policy site; callers pass getattr(cfg, ..., None)
    if not decode:
        wte = lookup_table_view(wte)
    if onehot_grad and not decode:
        return take_embed_onehot_grad(wte, ids)
    return jnp.take(wte, ids, axis=0)


def config_from(table: dict, cls, name: str, **overrides):
    """Look up a named config dict and build ``cls`` with overrides."""
    base = dict(table[name])
    base.update(overrides)
    return cls(**base)


def attention_geometry_kwargs(cfg):
    """Per-model flash-attention geometry overrides, zoo-shared.

    ``cfg.attention_blocks`` is a spec string (the grammar of
    ``ops/pallas/attention_geometry.parse_spec``, e.g.
    ``"block_q=256,block_k=512,policy=recompute"`` — a string so frozen
    model configs stay hashable). Returns ``dot_product_attention`` kwargs
    for the flash backend, ``{}`` otherwise: the XLA/ring backends have no
    block geometry and must not receive the kwargs. Passed as
    ``geometry_spec`` (not direct block kwargs) so the pinned blocks CLAMP
    to each call shape's divisors instead of knocking untileable shapes
    off the kernel; unset fields still resolve through the engine config /
    env / autotune-cache layers inside the kernel."""
    spec = getattr(cfg, "attention_blocks", None)
    if not spec or getattr(cfg, "attention_backend", "xla") != "flash":
        return {}
    return {"geometry_spec": spec}


def normalize_padding_mask(attention_mask, ndim_target: int = 4):
    """[B, L] 0/1 padding mask → [B, 1, 1, L] boolean; pass through masks
    that already have a broadcastable rank."""
    if attention_mask is None:
        return None
    if attention_mask.ndim == 2:
        return attention_mask[:, None, None, :].astype(bool)
    return attention_mask.astype(bool)


@functools.lru_cache(maxsize=None)
def _fused_lm_head_loss_fn(vocab: int, x_dtype_name: str, w_dtype_name: str,
                           chunk: int, ignore_index: int, vocab_major: bool,
                           has_bias: bool = False):
    """Chunked LM-head + cross-entropy with a custom VJP.

    Computes mean next-token NLL from HIDDEN STATES without ever
    materializing the [B, T, V] logits (the largest allocation of a
    causal-LM train step: 2 x 1.5 GiB at mb16/seq1024/GPT-2 vocab, and far
    worse for 32k-152k-vocab families). Token chunks of size ``chunk``
    stream through a lax.scan: forward keeps only per-token lse / label
    logits; backward recomputes each chunk's logits and feeds the
    (softmax - onehot) cotangent straight into the two matmuls.

    Math matches ``models.gpt2.cross_entropy_loss`` applied to
    ``einsum('bte,ve->btv', x, W)``: logits at the compute dtype, fp32
    reductions (sub-ulp reduction-order differences only). Replaces the
    reference's fused softmax-xent CUDA path the TPU way — XLA fuses each
    chunk's convert/exp/mask into the matmuls, no hand-written kernel
    needed.
    """
    x_dtype = jnp.dtype(x_dtype_name)

    def _chunks(arr, c):
        return arr.reshape((-1, c) + arr.shape[1:])

    def _pad_tokens(x_f, lab_f):
        n = x_f.shape[0]
        pad = (-n) % chunk
        if pad:
            x_f = jnp.concatenate([x_f, jnp.zeros((pad, x_f.shape[1]), x_f.dtype)])
            lab_f = jnp.concatenate(
                [lab_f, jnp.full((pad,), ignore_index, lab_f.dtype)])
        return x_f, lab_f

    # weight layout: [V, E] (tied embedding, GPT-2) or [E, V] (untied
    # Dense head, LLaMA) — contraction dims differ, no transpose copies
    w_contract = (1,) if vocab_major else (0,)

    def _chunk_logits(x_c, w, bias):
        out = jax.lax.dot_general(x_c, w, (((1,), w_contract), ((), ())),
                                  preferred_element_type=x_dtype)  # [C, V]
        return out + bias if has_bias else out

    @jax.custom_vjp
    def f(x, w, bias, labels):
        out, _ = fwd(x, w, bias, labels)
        return out

    def fwd(x, w, bias, labels):
        b, t, e = x.shape
        x_f, lab_f = _pad_tokens(x.reshape(-1, e), labels.reshape(-1))
        valid_all = lab_f != ignore_index
        denom = jnp.maximum(jnp.sum(valid_all), 1).astype(jnp.float32)

        def body(acc, xs):
            x_c, lab_c = xs
            logits = _chunk_logits(x_c, w, bias)
            valid = lab_c != ignore_index
            safe = jnp.where(valid, lab_c, 0)
            logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
            nll = (logz - ll.astype(jnp.float32)) * valid
            return acc + nll.sum(), None

        total, _ = jax.lax.scan(body, jnp.zeros([], jnp.float32),
                                (_chunks(x_f, chunk), _chunks(lab_f, chunk)))
        return total / denom, (x, w, bias, labels, denom)

    def bwd(res, g):
        x, w, bias, labels, denom = res
        b, t, e = x.shape
        x_f, lab_f = _pad_tokens(x.reshape(-1, e), labels.reshape(-1))
        scale = g / denom

        def body(carry, xs):
            dw_acc, db_acc = carry
            x_c, lab_c = xs
            logits = _chunk_logits(x_c, w, bias)
            valid = lab_c != ignore_index
            safe = jnp.where(valid, lab_c, 0)
            p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            coeff32 = p - jax.nn.one_hot(safe, vocab, dtype=jnp.float32)
            coeff32 = coeff32 * (valid * scale)[:, None]  # [C, V]
            coeff = coeff32.astype(x_dtype)
            dx_c = jax.lax.dot_general(
                coeff, w, (((1,), (0,) if vocab_major else (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if vocab_major:
                dw_c = jax.lax.dot_general(coeff, x_c, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
            else:
                dw_c = jax.lax.dot_general(x_c, coeff, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
            db_acc = db_acc + coeff32.sum(0) if has_bias else db_acc
            return (dw_acc + dw_c, db_acc), dx_c.astype(x.dtype)

        dw_shape = (vocab, e) if vocab_major else (e, vocab)
        db0 = jnp.zeros((vocab,), jnp.float32) if has_bias else jnp.zeros([], jnp.float32)
        (dw, db), dx_chunks = jax.lax.scan(
            body, (jnp.zeros(dw_shape, jnp.float32), db0),
            (_chunks(x_f, chunk), _chunks(lab_f, chunk)))
        dx = dx_chunks.reshape(-1, e)[:b * t].reshape(b, t, e)
        db_out = db.astype(jnp.dtype(w_dtype_name)) if has_bias else None
        return dx, dw.astype(jnp.dtype(w_dtype_name)), db_out, None

    f.defvjp(fwd, bwd)
    return f


def fused_lm_head_loss(x, embedding, labels, *, bias=None, chunk: int = 1024,
                       ignore_index: int = -100, vocab_major: bool = True):
    """Mean next-token cross-entropy straight from hidden states.

    ``x``: [B, T, E] hidden states (already shifted — token t predicts
    ``labels[:, t]``); ``embedding``: the LM head at the compute dtype —
    [V, E] tied embedding (``vocab_major=True``, GPT-2) or [E, V] untied
    Dense kernel (``vocab_major=False``, LLaMA); ``bias``: optional [V]
    head bias at the compute dtype (GPT-J), added per chunk with its grad
    accumulated in the backward scan; ``labels``: [B, T] int with
    ``ignore_index`` masking. See ``_fused_lm_head_loss_fn`` for the
    memory story.
    """
    vocab = int(embedding.shape[0] if vocab_major else embedding.shape[1])
    fn = _fused_lm_head_loss_fn(vocab,
                                jnp.dtype(x.dtype).name,
                                jnp.dtype(embedding.dtype).name,
                                int(chunk), int(ignore_index), bool(vocab_major),
                                bias is not None)
    return fn(x, embedding, bias, labels)


def fused_head_loss_output(x, weight, labels, aux_total, deterministic, cfg, *,
                           vocab_major: bool, bias=None):
    """Shared fused-head dispatch for causal-LM model families: applies the
    next-token shift, runs :func:`fused_lm_head_loss`, and adds the MoE aux
    loss in training only (eval reports pure CE, matching the engine's
    unfused eval branch). Keeping the shift convention and aux policy here
    means every family adopting ``fused_head_loss_chunk`` stays in
    lockstep."""
    loss = fused_lm_head_loss(x[:, :-1], weight, labels[:, 1:], bias=bias,
                              chunk=cfg.fused_head_loss_chunk,
                              vocab_major=vocab_major)
    if getattr(cfg, "moe_num_experts", 0) > 0 and not deterministic:
        loss = loss + aux_total * cfg.moe_aux_loss_coef
    return loss


class UntiedHeadKernel(nn.Module):
    """Declares an untied LM-head kernel at the same param path as
    ``nn.Dense(name=<name>)`` ([E, V], same init/partitioning) so a fused-
    loss branch shares weights with the logits branch (used by LLaMA's
    ``lm_head`` and GPT-NeoX's ``embed_out``). With ``use_bias`` it also
    declares the Dense-compatible bias and returns ``(kernel, bias)``
    (GPT-J's biased head)."""

    in_features: int
    out_features: int
    param_dtype: Any = jnp.float32
    use_bias: bool = False

    @nn.compact
    def __call__(self):
        unbox = lambda p: p.value if isinstance(p, nn.meta.AxisMetadata) else p
        kernel = unbox(self.param(
            "kernel", nn.with_logical_partitioning(dense_init(), ("embed", "vocab")),
            (self.in_features, self.out_features), self.param_dtype))
        if not self.use_bias:
            return kernel
        bias = unbox(self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros, ("vocab",)),
            (self.out_features,), self.param_dtype))
        return kernel, bias
