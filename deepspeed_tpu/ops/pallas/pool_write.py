"""A tick's write into stored pools, as one kernel: a token a sequence (a
decode tick) or a piece of a chunk (at most a window's tokens) into the leaves
of a serving cache **as they are stored** (pools [slots, heads, head dim,
positions] and, of int8 pools, their scales [slots, heads, positions];
positions minor-most), every leaf of a layer in one call.

A position is one lane of a 128-lane window, so the least the chip can rewrite
is the aligned 128-position window that holds it (two for a piece, which may
straddle a boundary): a grid step a sequence a window brings that window of
each leaf into VMEM, puts the tokens' values on their lanes and stores the
window back where it lay (the leaves are aliased to the
outputs, so what no step visits is untouched). Every serving cache's write on
a TPU comes here, piece by piece, wherever :func:`takes` holds
(``models/common.py`` ``_append_in_place``, under ``slot_pool_append`` and
``ring_pool_append``): a choice by the shapes, and by the configurations' own
shapes that is every write of every serving family. What it refuses goes to
``_append_piece`` there, which does the same a slot at a time in a
``fori_loop`` of scalar-indexed slices, a dozen microseconds a slot a layer
whatever the bytes; here the steps' DMAs run ahead of each other (``PERF.md``
section 6: PR 50, 0.6 of a looped stack's decode tick was that loop, 192 of
them a tick; PR 56, the other families).

The select runs on 32-bit words (:func:`pltpu.bitcast` packs four int8 rows or
two bfloat16 rows of a window into one: the lane mask is the same for all of
them), which every TPU generation's vector unit has. A sequence whose position
is at or past the extent (a parked slot) rewrites its last windows as they were.

``part`` of ``parts`` (``DecodeCache`` ``parts``: a looped stack's cache a
pass): the leaves hold ``parts`` times the updates' heads and the write goes to
pass ``part``'s, the block along the head axis. Serving only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend

#: positions of a written window: one lane row of the TPU's tiling
WINDOW = 128


def takes(leaves, updates) -> bool:
    """Whether :func:`pool_write` can make this write: at most a window of
    tokens a sequence, an extent of whole lane rows that holds the span, and
    rows that pack into whole 32-bit words."""
    length, places = updates[0].shape[1], leaves[0].shape[-1]
    if length > WINDOW or places % WINDOW or places < _span(length):
        return False
    for leaf, upd in zip(leaves, updates):
        packed = 4 // leaf.dtype.itemsize
        if leaf.dtype.itemsize > 4 or upd.shape[-1] % max(packed, 1) or leaf.ndim not in (3, 4):
            return False
    return True


def _span(length: int) -> int:
    """Positions a piece of ``length`` tokens may touch: its window, or two,
    so that it may straddle a boundary (``models/common.py`` ``_append_span``)."""
    return WINDOW if length == 1 else 2 * WINDOW


def _kernel(row_ref, first_ref, pos_ref, *refs, n_leaves, looped, length):
    if looped:
        refs = refs[1:]                 # the pass: the index maps' business
    wins, olds, outs = (refs[i * n_leaves:(i + 1) * n_leaves] for i in range(3))
    s, w = pl.program_id(0), pl.program_id(1)
    # token j of the sequence lies at position pos + j: which of this window's
    # lanes that is (none of a parked sequence's: its position is past them all)
    before = (first_ref[s] + w) * WINDOW - pos_ref[s]
    for win_ref, old_ref, out_ref in zip(wins, olds, outs):
        old, win = old_ref[...], win_ref[...]
        if old.dtype.itemsize < 4:
            old, win = pltpu.bitcast(old, jnp.int32), pltpu.bitcast(win, jnp.int32)
        j = before + jax.lax.broadcasted_iota(jnp.int32, old.shape, old.ndim - 1)
        new = jnp.where((j >= 0) & (j < length), win, old)
        out_ref[...] = new if new.dtype == out_ref.dtype else pltpu.bitcast(new, out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def pool_write(leaves, updates, pos, rows=None, part=None, parts: int = 1, interpret=None):
    """``updates[i]`` [n, l, heads, ...] (token-major, as the projections
    produce them: [n, l, heads, head dim] for a pool, [n, l, heads] for its
    scales; ``l`` at most a window) written at positions ``pos[s] .. pos[s] + l
    - 1`` of slot ``rows[s]`` (None: ``s``) of the stored ``leaves[i]`` [slots,
    heads (x parts), ..., positions]; returns the new leaves, the old ones'
    buffers where the caller donates them. A position at or past the extent
    writes nothing; tokens past the extent are dropped."""
    n, length = updates[0].shape[:2]
    places, span = leaves[0].shape[-1], _span(length)
    windows = span // WINDOW
    if interpret is None:
        interpret = backend.interpret_default()
    pos = pos.astype(jnp.int32)
    first = jnp.clip(pos // WINDOW, 0, places // WINDOW - windows)
    row = jnp.arange(n, dtype=jnp.int32) if rows is None else jnp.asarray(rows, jnp.int32)
    looped = parts > 1
    prefetch = [row, first, pos] + ([jnp.asarray(part, jnp.int32).reshape(1)] if looped else [])

    def laid_out(leaf, upd):
        """``upd`` as the leaf lays it out over the span, token j of sequence
        s on lane ``pos[s] + j - first[s] * WINDOW`` (one token: on every lane)."""
        upd = jnp.moveaxis(upd.astype(leaf.dtype), 1, -1)
        if length == 1:
            return jnp.broadcast_to(upd, upd.shape[:-1] + (span,))
        upd = jnp.pad(upd, [(0, 0)] * (upd.ndim - 1) + [(0, span - length)])
        return jax.vmap(lambda x, by: jnp.roll(x, by, axis=-1))(upd, pos - first * WINDOW)

    wins = [laid_out(leaf, upd) for leaf, upd in zip(leaves, updates)]

    def stored(leaf, win):
        middle = (0,) * (leaf.ndim - 3)

        def at(s, w, row_, first_, _pos, *part_):
            return (row_[s], part_[0][0] if looped else 0, *middle, first_[s] + w)
        return pl.BlockSpec((None,) + win.shape[1:-1] + (WINDOW,), at)

    def mine(win):
        return pl.BlockSpec((None,) + win.shape[1:-1] + (WINDOW,),
                            lambda s, w, *_: (s,) + (0,) * (win.ndim - 2) + (w,))

    pools = [stored(leaf, win) for leaf, win in zip(leaves, wins)]
    out = pl.pallas_call(
        functools.partial(_kernel, n_leaves=len(leaves), looped=looped, length=length),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(n, windows),
            in_specs=[mine(win) for win in wins] + pools, out_specs=pools),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype) for leaf in leaves],
        input_output_aliases={len(prefetch) + len(wins) + i: i for i in range(len(leaves))},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="pool_write",
    )(*prefetch, *wins, *leaves)
    return list(out)
