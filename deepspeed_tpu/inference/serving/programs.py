"""Fixed-shape serving programs over a per-slot (ragged) decode cache.

The compiled surface of graft-serve is THREE programs per slot bucket —
a chunked prefill, a one-token decode step, and (with speculation) a
k+1-token verify step — whose shapes never change while requests join
and leave. Join/leave is positional, not structural: every program takes
the scheduler's host-side length mirror as ONE operand, ``write_pos
[slots] int32``, and fills the cache's index leaves from it inside the
trace (:func:`with_write_positions`) — the host never touches the cache
between ticks, and what the carried index leaves hold is ignored. A
parked slot carries the sentinel position ``n_positions`` so its KV
writes drop out of bounds and its (garbage, finite) logits are discarded
on the host. Rollback after a rejected speculation is therefore free —
the next tick's operand simply doesn't advance past the accepted prefix.

A tick feeds few slots when few requests are in flight, and a program over
every slot then computes, writes and reads mostly parked ones. So the prefill
program and the plain decode program also exist over fewer sequences, each at
a ladder of two sizes ("rungs": one builder a program, the size read from the
operands): a quarter of the slots, and all (:func:`prefill_rungs`; for decode
only where a quarter is at least 8 sequences, :func:`decode_rungs`). A rung
below the whole is handed ``slot_ids [n] int32``, the slot of each sequence it
runs, and ``write_pos`` (a prefill rung's ``ids`` and ``last_idx`` too) of
those ``n`` alone. The model then sees a batch of ``n``; only what holds a
row a slot is indexed (:func:`rows_of_slots`), and each fed slot's next token
is left in the cache (:data:`TOKEN_LEAF`) for whichever size runs next. One
rule decides for both: a tick runs the smallest rung that holds the slots it
feeds, from the tick's own fed count; there is nothing to set. What a rung
costs is a start: a trace of the model, a lowering and an executable to load
in ``warmup`` (about three seconds warm for GPT-2 medium, half of it the
load) and its code on the device. The rung over every slot is the program above, with nothing indexed,
and the only one over a latent pool or on a mesh; a speculating scheduler's
verify and a drafter's decode have the whole shape alone.

Programs are cached on the target :class:`InferenceEngine` keyed by the
pow2 slot bucket (``engine._pow2_bucket`` — the same bucketing discipline
as ``generate``), so schedulers and repeated deployments reuse
compilations instead of churning them.
"""

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

# the cache's leaf names are the models' (``models/common.py`` DecodeCache):
# INDEX_LEAVES hold write positions (scalar in ``generate``'s lockstep
# cache; [slots] vectors in the serving cache), KV_LEAVES are the pools of
# keys and values, POOL_LEAVES those and a latent-attention layer's one
# ``cached_latent`` pool (``models/common.py`` LatentCache): every pool that
# holds rows by position (an indexed layer's ``cached_index_key`` beside its
# latent among them). RING_LEAVES are a window layer's latent, or its keys and
# values (RING_KV_LEAVES, ``models/llama.py``): the stored form of a pool over a
# ring of positions, written at ``position mod ring``; it is no pool (it has
# lost most positions' rows) and its extent is not the slots' capacity
# a model with recurrent layers (``models/nemotron_h.py``) adds STATE_LEAVES
# (per-slot state with no positions, ``[slots, ...]`` as the model shapes
# it), LENGTH_LEAVES (how many of a slot's tokens this tick are real) and,
# where a layer counts for the host, COUNTER_LEAVES
from deepspeed_tpu.models.common import (COUNTER_LEAVES, INDEX_KEY_LEAVES, INDEX_LEAVES,
                                         KV_LEAVES, LATENT_LEAVES, LENGTH_LEAVES, POOL_LEAVES,
                                         RING_KV_LEAVES, RING_LEAVES, SLOT_LEAF, STATE_LEAVES,
                                         slot_pool,
                                         slot_pool_positions, slot_pool_scale)
from deepspeed_tpu.utils import trace


#: the cache's one leaf that is no model's: the token each slot is fed next
TOKEN_LEAF = "next_token"

#: the fewest sequences a decode rung below the whole runs (:func:`decode_rungs`)
DECODE_RUNG_FLOOR = 8


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", None) or str(last)


def _is_index_leaf(path) -> bool:
    return _leaf_name(path) in INDEX_LEAVES


def _leaves_named(cache, names):
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
            if _leaf_name(path) in names]


def make_slot_cache(module, slots: int, kv_quant: bool = False):
    """A per-slot serving cache: the model's decode cache with every index
    leaf widened from a scalar to a [slots] vector (which is what flips
    the model's decode branch to per-slot writes + per-slot
    ``decode_lengths``) and every KV pool in the stored form of
    ``models/common.py`` ``slot_pool`` (positions minor-most, which is what
    lets a tick write a token in place). Slots start PARKED (sentinel
    position).

    ``kv_quant=True`` (the ``ServingConfig.kv_quant`` serving default)
    converts the KV pools to int8 codes and adds a
    ``<leaf>_scale [slots, H, P]`` companion per pool — the provided
    cache dtype is what statically flips the model's decode branch to
    quantize-on-write / dequantize-on-read.

    A recurrent layer's state (``STATE_LEAVES``) stays ``[slots, ...]`` as
    the model shapes it, zeroed and never quantised; the ``LENGTH_LEAVES``
    beside the index leaves are [slots] vectors too, 0 for a parked slot.
    A window layer's ring (``RING_LEAVES``) is stored as a pool is, over its
    own, shorter extent: the cache's pools are then sized by the kind of layer
    (every position for a full layer's latent and index keys, a window and a
    chunk for a window layer's), and the parked sentinel is the POOLS' extent
    (:func:`slot_capacity`), which a ring's write is told apart from by the
    length leaf's 0.
    :data:`TOKEN_LEAF` lies at the top level, zeros."""
    from deepspeed_tpu.models.common import init_cache

    def stored(cache):
        def leaf_of(path, leaf):
            if _is_index_leaf(path) or _leaf_name(path) in LENGTH_LEAVES:
                return jnp.zeros((slots,), jnp.int32)
            return slot_pool(leaf) if _leaf_name(path) in POOL_LEAVES + RING_LEAVES else leaf

        cache = jax.tree_util.tree_map_with_path(leaf_of, cache)
        return quantize_slot_cache(cache) if kv_quant else cache

    # shapes first, then each stored leaf once: set-up never holds the
    # lockstep pools, nor fp pools beside their int8 form
    shapes = jax.eval_shape(lambda: stored(init_cache(module, slots)))
    parked = slot_capacity(shapes)
    cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full(leaf.shape, parked if _is_index_leaf(path) else 0,
                                    leaf.dtype), shapes)
    return with_next_tokens(cache, jnp.zeros((slots,), jnp.int32))


def quantize_slot_cache(cache):
    """int8-KV view of a (fresh) slot cache: each KV pool becomes int8
    codes and gains a per-(slot, head, position) scale leaf in the pool's
    original dtype. Zero scales on parked/unwritten rows dequantize to the
    zeros the fp cache would hold."""

    def walk(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict) or hasattr(leaf, "items"):
                out[name] = walk(leaf)
            elif name in KV_LEAVES + RING_KV_LEAVES:
                out[name] = jnp.zeros(leaf.shape, jnp.int8)
                out[name + "_scale"] = slot_pool_scale(leaf)
            elif name in LATENT_LEAVES + RING_LEAVES:
                raise NotImplementedError(
                    f"kv_quant over a latent pool ({name}): an int8 latent is not built (every "
                    f"head reads it through two projections, so its tolerance is its own); "
                    f"serve this model with kv_quant=False")
            elif name in INDEX_KEY_LEAVES:
                raise NotImplementedError(
                    f"kv_quant over an indexer's keys ({name}): int8 index keys are not built "
                    f"(they decide WHICH positions are attended, so their tolerance is a set's, "
                    f"not a logit's); serve this model with kv_quant=False")
            else:
                out[name] = leaf
        return out

    return walk(cache)


def slot_capacity(cache) -> int:
    """Token capacity per slot of a serving cache = its KV pools' position
    extent (also the parked-slot sentinel: a write at this position drops
    out of bounds). A ring (``RING_LEAVES``) is no pool: its extent bounds
    no slot."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if _leaf_name(path) in POOL_LEAVES:
            return slot_pool_positions(leaf)
    raise ValueError("cache has no cached_key or cached_latent leaves — not a decode cache")


def with_write_positions(cache, write_pos, fed=1):
    """Traced: ``cache`` with every index leaf set to the tick's
    ``write_pos [slots] int32`` operand — one value used by each block's
    ``cache_index`` and the model's ``position_index``, no transfer. The
    carried leaves' own values are dead (each comes back a fresh output
    buffer, so the donated cache still chains tick to tick).

    A cache with ``LENGTH_LEAVES`` (a model with recurrent state) also gets
    how many of each slot's tokens are real: ``fed`` (a scalar or [slots]),
    and 0 for a parked slot, whose state must come back untouched."""

    if not _leaves_named(cache, LENGTH_LEAVES):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: write_pos if _is_index_leaf(path) else leaf, cache)
    length = jnp.where(write_pos < slot_capacity(cache), fed, 0).astype(jnp.int32)

    def sub(path, leaf):
        if _leaf_name(path) in LENGTH_LEAVES:
            return length
        return write_pos if _is_index_leaf(path) else leaf

    return jax.tree_util.tree_map_with_path(sub, cache)


def without_next_tokens(cache):
    """``(the cache as the model knows it, next_token [slots])``."""
    return {name: leaf for name, leaf in cache.items() if name != TOKEN_LEAF}, cache[TOKEN_LEAF]


def with_next_tokens(cache, tokens):
    """``cache`` (the model's) with :data:`TOKEN_LEAF` laid beside it."""
    return {**cache, TOKEN_LEAF: tokens}


def prefill_rungs(slots: int, mesh_size: int = 1, cache=None) -> tuple:
    """The sequence counts the prefill program is built at, ascending, the
    last every slot: a quarter of the slots, and all. Two, because a rung is
    a program: a model's trace, a lowering and an executable to load at
    set-up (2.3-6.6 s warm in the benchmark's cells) and its code on the
    device (94-116 MB for GPT-2 medium); the chip read a half rung as worth
    less than that (``PERF.md`` section 6, PR 33). Only all:

    * on a mesh of more than one device: the slots are sharded over ``data``
      there, and a row picked by number would cross devices;
    * where ``cache`` holds a latent pool: its prefill attention already
      walks the fed slots alone, a block of a slot's pool at a time, which is
      two thirds of that tick, so a rung has the least to spare where it
      costs set-up the most (the long-document cell: 6.6 s of 64, no gain)."""
    latent = cache is not None and bool(_leaves_named(cache, LATENT_LEAVES))
    if mesh_size > 1 or slots < 4 or latent:
        return (slots,)
    return (slots // 4, slots)


def decode_rungs(slots: int, mesh_size: int = 1, cache=None) -> tuple:
    """The sequence counts the plain decode program is built at, ascending,
    the last every slot: :func:`prefill_rungs`' rule (a quarter and all; all
    alone on a mesh and over a latent pool, for its two reasons) with a floor,
    a quarter of **at least 8 sequences**. A decode row is one position, so
    below 8 rows (one sublane tile of fp32) the tick's matmuls and its
    weights' stream are what they were, and a scheduler of under 32 slots has
    no quarter worth a program's set-up."""
    rungs = prefill_rungs(slots, mesh_size, cache)
    return rungs if rungs[0] >= DECODE_RUNG_FLOOR else (slots,)


def rows_of_slots(cache, slot_ids):
    """Traced: ``cache`` as a model sees it that runs the ``n`` sequences
    ``slot_ids``: the recurrent state (``STATE_LEAVES``) of those slots,
    gathered to ``[n, ...]``, and ``slot_ids`` laid beside every pool
    (``SLOT_LEAF``), so that ``DecodeCache`` indexes the pools' rows where it
    writes and reads them: no pool is gathered here.
    Index and length leaves are ``[n]`` already (:func:`with_write_positions`)."""

    def walk(tree):
        if any(name in LATENT_LEAVES for name in tree):
            raise NotImplementedError("a latent pool is read in place, a slot at a time: "
                                      "the prefill program over it has one size, every slot")
        out = {name: walk(leaf) if hasattr(leaf, "items")
               else leaf[slot_ids] if name in STATE_LEAVES else leaf
               for name, leaf in tree.items()}
        if any(name in KV_LEAVES + RING_KV_LEAVES for name in tree):
            out[SLOT_LEAF] = slot_ids
        return out

    return walk(cache)


def rows_to_slots(cache, ran, slot_ids):
    """Traced: the whole cache after a model ran :func:`rows_of_slots` of
    it: what came back ``[n, ...]`` (state, index and length leaves) put into
    rows ``slot_ids`` of ``cache``'s, the other slots' rows as they were; the
    pools, written in place by row, and the counters as they came back."""
    by_row = STATE_LEAVES + INDEX_LEAVES + LENGTH_LEAVES

    def walk(old, new):
        return {name: walk(leaf, new[name]) if hasattr(leaf, "items")
                else leaf.at[slot_ids].set(new[name]) if name in by_row else new[name]
                for name, leaf in old.items()}

    return walk(cache, ran)


def with_counters(cache, tok):
    """Traced: what a tick reads back. ``tok`` [slots] int32 (a rung's: one
    a sequence it ran), and behind it
    the cache's ``COUNTER_LEAVES`` (int32 vectors a layer left for the host:
    ``MOELayer.experts_held``'s rows, a latent-attention layer's reads, a walked
    pool's, ``kv_reads``), each
    name's summed over the layers, in ``COUNTER_LEAVES``' order, where the
    model has any, so that the one read-back a tick makes carries them."""
    counters = [sum(leaves) for name in COUNTER_LEAVES
                if (leaves := _leaves_named(cache, (name,)))]
    return jnp.concatenate([tok, *counters]) if counters else tok


def counter_widths(cache):
    """``(name, int32s)`` of what :func:`with_counters` appends, in order."""
    return [(name, leaves[0].shape[0]) for name in COUNTER_LEAVES
            if (leaves := _leaves_named(cache, (name,)))]


def has_ring(cache) -> bool:
    """Whether a serving cache holds a window layer's ring: positions it has
    overwritten cannot be copied out of it."""
    return bool(_leaves_named(cache, RING_LEAVES))


def has_recurrent_state(cache) -> bool:
    """Whether a serving cache holds per-slot state with no positions."""
    return bool(_leaves_named(cache, STATE_LEAVES))


def state_bytes_per_slot(cache) -> int:
    """Bytes of recurrent state (``STATE_LEAVES``) one slot holds."""
    return sum(leaf.size * leaf.dtype.itemsize // leaf.shape[0]
               for leaf in _leaves_named(cache, STATE_LEAVES))


def _tokens_of_fed(held, tok, fed, slot_ids=None):
    """Traced: :data:`TOKEN_LEAF` after a program sampled ``tok``, one a row
    it ran (``slot_ids`` where it ran a rung): the token of each row it
    ``fed``; a row parked at the sentinel leaves its slot's token as it was
    (an active slot sits out a prefill tick so)."""
    if slot_ids is None:
        return jnp.where(fed, tok, held)
    return held.at[slot_ids].set(jnp.where(fed, tok, held[slot_ids]))


# ---------------------------------------------------------------------------
# step builders: apply_fn(params, cache, ids) -> (logits [S, L, V], cache').
# Every built step takes ``write_pos [slots] int32`` right after the cache.
# ---------------------------------------------------------------------------
def make_apply_fn(module, mparams: Optional[Callable] = None) -> Callable:
    """The one decode apply shared by every serving program (and by the
    ``serve_decode_step``/``serve_quant_decode_step`` audit scenarios, so
    the gated program IS the served one). ``mparams`` is the engine's
    runtime weight view hook (int8 dequant); identity when absent.

    A weight-quantized serving path passes ``params`` as the bundle
    ``{"params": codes, "quant": scales}`` (``quantize_params`` output);
    the quant collection rides into ``module.apply`` so projections read
    their scales via ``get_variable("quant", "kernel_scale")``."""
    mp = mparams or (lambda p: p)

    def apply_fn(params, cache, ids):
        if isinstance(params, dict) and "quant" in params and "params" in params:
            variables = {"params": mp(params["params"]),
                         "quant": params["quant"], "cache": cache}
        else:
            variables = {"params": mp(params), "cache": cache}
        out, upd = module.apply(variables, ids, decode=True, mutable=["cache"])
        logits = out[0] if isinstance(out, (tuple, list)) else out
        return logits, upd["cache"]

    return apply_fn


def build_prefill_step(apply_fn, do_sample: bool, temperature: float,
                       top_k: int, top_p: float, rung: bool = False) -> Callable:
    """One chunked-prefill tick: consume ``ids [S, C]`` at each slot's own
    write position (``write_pos [S]``). ``last_idx [S]`` names each slot's
    final REAL token in the chunk (a short final chunk is right-padded; pad
    positions write beyond the committed length, are re-written by later
    tokens, and —
    because the per-slot causal mask bounds every query by its own
    position — are never attended by real queries; a recurrent layer, which
    has no positions to overwrite, is handed ``last_idx + 1`` as the slot's
    real length and does not advance past it). The chunk that
    completes a prompt samples the request's FIRST token from its
    last-real-position logits, so TTFT stops at prefill completion. The token
    sampled for each fed row is also left in the cache's :data:`TOKEN_LEAF`,
    where the slot's first decode tick finds it.

    ``rung=True`` builds the program over fewer sequences than slots: it
    takes ``slot_ids [n] int32`` before ``write_pos`` (distinct slots; the
    operands behind it are ``[n]`` and ``[n, C]``; an entry that only fills
    the rung is a slot parked at the sentinel, which writes nothing as a
    parked slot of the whole program writes nothing) and returns ``n``
    tokens. The cache goes in and comes back whole."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine import sample_logits

    def prefill(params, cache, write_pos, ids, last_idx, *rng, slot_ids=None):
        cache, held = without_next_tokens(cache)
        fed = with_write_positions(cache, write_pos, last_idx + 1)
        if slot_ids is None:
            logits, cache = apply_fn(params, fed, ids)
        else:
            logits, ran = apply_fn(params, rows_of_slots(fed, slot_ids), ids)
            cache = rows_to_slots(cache, ran, slot_ids)
        if logits.shape[1] == 1:
            # a model that makes a chunk's logits for its last real token alone
            # (``LlamaConfig.head_last_fed_only``), or a chunk of one token
            logits = logits[:, 0]
        else:
            logits = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]
        if do_sample:
            tok = sample_logits(logits, *rng, True, temperature, top_k, top_p)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = tok.astype(jnp.int32)
        held = _tokens_of_fed(held, tok, write_pos < slot_capacity(cache), slot_ids)
        return with_next_tokens(cache, held), with_counters(cache, tok)

    if not rung:
        return prefill

    def prefill_rung(params, cache, slot_ids, *operands):
        return prefill(params, cache, *operands, slot_ids=slot_ids)

    return prefill_rung


def build_decode_step(apply_fn, do_sample: bool, temperature: float,
                      top_k: int, top_p: float, rung: bool = False) -> Callable:
    """One decode tick: feed each slot the token the cache holds for it
    (:data:`TOKEN_LEAF`; a parked slot is fed 0) at its write position, sample
    the next and leave it there. Greedy builds a no-rng program
    (``decode(params, cache, write_pos)``); sampling adds an rng operand.
    ``tokens [slots] int32``, by keyword, is fed in the leaf's place: a draft
    loop's first token is the host's, the last the target accepted, and each
    later one what the step before returned, so a step fed by keyword returns
    its tokens alone, no counter behind them (whoever drafts reads none).

    ``rung=True`` builds the program over fewer sequences than slots, as
    :func:`build_prefill_step` does: ``decode_rung(params, cache, slot_ids,
    write_pos, *rng)`` with ``slot_ids [n] int32`` distinct slots and
    ``write_pos [n]`` theirs (an entry that only fills the rung is parked at
    the sentinel). It feeds row ``j`` the token the cache holds for slot
    ``slot_ids[j]``, writes and reads those slots' pool rows in place, returns
    ``n`` tokens and leaves each fed slot's in :data:`TOKEN_LEAF`, so the next
    program, of either size, finds it there. The cache goes in and comes back
    whole; ``tokens=`` stays the whole program's."""
    from deepspeed_tpu.inference.engine import sample_logits

    def decode(params, cache, write_pos, *rng, tokens=None, slot_ids=None):
        if len(rng) != int(do_sample):
            raise TypeError(f"decode takes {int(do_sample)} rng operand(s) behind write_pos, "
                            f"got {len(rng)}: a slot's token is the cache's, or ``tokens=``")
        cache, held = without_next_tokens(cache)
        live = write_pos < slot_capacity(cache)
        drafts = tokens is not None
        if not drafts:
            tokens = jnp.where(live, held if slot_ids is None else held[slot_ids], 0)
        fed = with_write_positions(cache, write_pos)
        if slot_ids is None:
            logits, cache = apply_fn(params, fed, tokens[:, None])
        else:
            logits, ran = apply_fn(params, rows_of_slots(fed, slot_ids), tokens[:, None])
            cache = rows_to_slots(cache, ran, slot_ids)
        if do_sample:
            tok = sample_logits(logits[:, -1], *rng, True, temperature, top_k, top_p)
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)
        tok = tok.astype(jnp.int32)
        return (with_next_tokens(cache, _tokens_of_fed(held, tok, live, slot_ids)),
                tok if drafts else with_counters(cache, tok))

    if not rung:
        return decode

    def decode_rung(params, cache, slot_ids, write_pos, *rng):
        return decode(params, cache, write_pos, *rng, slot_ids=slot_ids)

    return decode_rung


def build_verify_step(apply_fn) -> Callable:
    """Batched target verification for speculative decoding: feed the
    k+1-token block ``[last_accepted, d_1..d_k]`` at ``write_pos`` and
    return the target's greedy token at EVERY position — the host accepts the longest draft
    prefix the target reproduces and emits the target's own token at the
    first divergence (lossless under greedy decoding by construction)."""

    def verify(params, cache, write_pos, tokens):
        cache, held = without_next_tokens(cache)
        logits, cache = apply_fn(params, with_write_positions(cache, write_pos,
                                                              tokens.shape[1]), tokens)
        return (with_next_tokens(cache, held),
                jnp.argmax(logits, axis=-1).astype(jnp.int32))  # [S, K+1]

    return verify


# ---------------------------------------------------------------------------
# engine-level program cache (satellite: serving reuses the bucketed cache)
# ---------------------------------------------------------------------------
def serve_programs(engine, slots_bucket: int, *, prefill_chunk: int,
                   do_sample: bool, temperature: float, top_k: int, top_p: float,
                   spec_k: int = 0, role: str = "target",
                   module=None, mparams=None,
                   weight_dtype: Optional[str] = None) -> Dict[str, Any]:
    """The serving program dict for one pow2 slot bucket, cached on the
    ENGINE (``engine._serve_cache``) so every scheduler over the same
    engine — and re-created schedulers across deployments — reuse the
    same compiled programs (the ``_pow2_bucket`` recompile-churn
    satellite counts exactly one program set per bucket).

    ``role``/``module`` let the speculation drafter park its own programs
    in the same cache under a distinct key; ``weight_dtype`` is the
    RESOLVED served weight dtype the caller will trace under — part of the
    key, so schedulers with different dtypes on one engine never share a
    program.

    The key carries the module's identity (the cached closures keep the
    module alive, so ``id`` cannot be recycled): two drafters with
    identical knobs but different modules must never share a compiled
    program closed over the first one's architecture. ``mparams`` is
    assumed determined by (engine, module) — identity for custom
    modules, the engine's weight view otherwise — and is not keyed."""
    if not hasattr(engine, "_serve_cache"):
        engine._serve_cache = {}
    mod = module if module is not None else engine.module
    key = (role, id(mod), int(slots_bucket), int(prefill_chunk), bool(do_sample),
           float(temperature), int(top_k), float(top_p), int(spec_k), weight_dtype)
    if key in engine._serve_cache:
        return engine._serve_cache[key]
    trace.recorder().count("serve_program_builds")
    apply_fn = make_apply_fn(mod,
                             mparams if mparams is not None else engine._mparams)
    jit_kwargs: Dict[str, Any] = {"donate_argnums": (1,)}
    if engine.mesh.size == 1:
        # On one device every placement is the same one, and jit then names an
        # output's after the first operand of its rank: an int8 scale leaf
        # [slots, heads, positions] comes back worded as some rank-3 weight's
        # ``P(None, 'tensor', None)``, not the ``P()`` the scheduler placed
        # the fresh cache with, and the first tick on that evolved cache is a
        # second compile (``test_ticks_put_at_most_once_and_never_retrace``).
        # So the cache is said to come back as it went in. Not on a mesh:
        # there the placement of the pools is GSPMD's to choose (heads over
        # ``tensor``), which a replicated ``P()`` here would undo.
        jit_kwargs["out_shardings"] = (NamedSharding(engine.mesh, PartitionSpec()), None)
    fns: Dict[str, Any] = {
        "prefill": jax.jit(build_prefill_step(apply_fn, do_sample, temperature,
                                              top_k, top_p), **jit_kwargs),
        "decode": jax.jit(build_decode_step(apply_fn, do_sample, temperature,
                                            top_k, top_p), **jit_kwargs),
    }
    if len(prefill_rungs(slots_bucket, engine.mesh.size)) > 1:
        # one jitted function for whatever rungs lie below the whole: the
        # operands' shapes are its cache's key, a program a rung
        fns["prefill_rung"] = jax.jit(build_prefill_step(apply_fn, do_sample, temperature,
                                                         top_k, top_p, rung=True),
                                      **jit_kwargs)
    if len(decode_rungs(slots_bucket, engine.mesh.size)) > 1:
        fns["decode_rung"] = jax.jit(build_decode_step(apply_fn, do_sample, temperature,
                                                       top_k, top_p, rung=True),
                                     **jit_kwargs)
    if spec_k > 0:
        fns["verify"] = jax.jit(build_verify_step(apply_fn), **jit_kwargs)
    engine._serve_cache[key] = fns
    return fns
